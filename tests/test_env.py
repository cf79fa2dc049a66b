"""`.env` loader + the shipped default `.env`."""

import os
from pathlib import Path

from progen_tpu.utils.env import load_env_file

REPO_ROOT = Path(__file__).resolve().parent.parent


class TestLoader:
    def test_parse_and_precedence(self, tmp_path, monkeypatch):
        f = tmp_path / ".env"
        f.write_text(
            "# comment\n"
            "export FOO=bar\n"
            "QUOTED='a b c'\n"
            "INLINE=x # trailing comment\n"
            "WINS=dotenv\n"
        )
        monkeypatch.setenv("WINS", "environ")
        saved = dict(os.environ)
        try:
            parsed = load_env_file(str(f))
            assert parsed["FOO"] == "bar" and os.environ["FOO"] == "bar"
            assert parsed["QUOTED"] == "a b c"
            assert parsed["INLINE"] == "x"
            # existing environment wins (dotenv override=False semantics)
            assert os.environ["WINS"] == "environ"
        finally:  # loader writes via setdefault: restore ALL keys it added
            os.environ.clear()
            os.environ.update(saved)

    def test_missing_file(self):
        assert load_env_file("/nonexistent/.env") == {}

    def test_dotenv_dir_expansion(self, tmp_path):
        # ${DOTENV_DIR} -> the .env file's own directory, keeping committed
        # repo-relative paths (XLA cache dir) checkout-path-agnostic
        f = tmp_path / ".env"
        f.write_text("CACHE=${DOTENV_DIR}/runs/xla_cache\n")
        saved = dict(os.environ)
        try:
            parsed = load_env_file(str(f))
        finally:
            os.environ.clear()
            os.environ.update(saved)
        assert parsed["CACHE"] == str(tmp_path.resolve() / "runs/xla_cache")

    def test_default_is_the_checkouts_env_not_the_cwds(self, tmp_path,
                                                       monkeypatch):
        # a stray .env in (or above) the CWD must not change what runs
        (tmp_path / ".env").write_text("STRAY_CWD_ENV=yes\n")
        monkeypatch.chdir(tmp_path)
        saved = dict(os.environ)
        try:
            parsed = load_env_file()
        finally:
            os.environ.clear()
            os.environ.update(saved)
        assert "STRAY_CWD_ENV" not in parsed
        assert parsed["JAX_COMPILATION_CACHE_DIR"] == str(
            REPO_ROOT / "runs/xla_cache"
        )


class TestCliCacheDir:
    """Started from any working directory, a CLI reports the same
    compile-cache directory: the exported JAX_COMPILATION_CACHE_DIR when
    there is one, else <checkout>/runs/xla_cache."""

    def _cache_dir_seen_by_cli(self, cwd, extra_env):
        import subprocess
        import sys

        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(PYTHONPATH=str(REPO_ROOT), **extra_env)
        # every CLI module loads the env file on import, before jax
        out = subprocess.run(
            [sys.executable, "-c",
             "import os, progen_tpu.cli.generate_data; "
             "print(os.environ['JAX_COMPILATION_CACHE_DIR'])"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        return out.stdout.strip()

    def test_unrelated_cwd_resolves_checkout_cache(self, tmp_path):
        assert self._cache_dir_seen_by_cli(tmp_path, {}) == str(
            REPO_ROOT / "runs/xla_cache"
        )

    def test_exported_cache_dir_wins(self, tmp_path):
        assert self._cache_dir_seen_by_cli(
            tmp_path, {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}
        ) == "/elsewhere/cache"


class TestShippedDefaultEnv:
    def test_exists_and_parses(self, monkeypatch):
        # parse WITHOUT mutating this process's environment
        env_path = REPO_ROOT / ".env"
        assert env_path.exists()
        saved = dict(os.environ)
        try:
            parsed = load_env_file(str(env_path))
        finally:
            os.environ.clear()
            os.environ.update(saved)
        assert parsed  # non-empty

        # TPU-only --xla_tpu_* names are FATAL inside XLA_FLAGS on CPU-only
        # hosts (parse_flags_from_env aborts the process) — they must ride
        # LIBTPU_INIT_ARGS instead. Regression-pin that invariant.
        assert "xla_tpu" not in parsed.get("XLA_FLAGS", "")
        assert "--xla_tpu_enable_async_collective_fusion" in parsed.get(
            "LIBTPU_INIT_ARGS", ""
        )
        # the shipped cache dir must resolve under THIS checkout, not a
        # hardcoded absolute path from someone else's machine
        assert parsed["JAX_COMPILATION_CACHE_DIR"] == str(
            REPO_ROOT / "runs/xla_cache"
        )
        # JAX's own default (1 s) decides what is worth caching: on the
        # chip most of serve's jits compile in under the 5 s this used to
        # demand, and a cold start recompiled them every time
        assert "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in parsed
