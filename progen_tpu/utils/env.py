"""Tiny ``.env`` loader — parity with the reference's ``load_dotenv()``
(/root/reference/train.py:1-2, sample.py:1-2; its ``.env`` carries XLA env
flags). python-dotenv is not in this image, and the needed subset is 10
lines: KEY=VALUE lines, ``#`` comments, optional ``export`` prefix,
existing environment wins (dotenv's default override=False).

The CLIs call this BEFORE they import jax. It is also where a process
that will compile says so: once the environment is set (the compile
cache's directory with it) the compile counters start listening
(``telemetry.compiles``), so they cover every compile the process makes,
the model's ``init`` included. That imports jax — never a backend — and
a caller that must stay jax-free (the router, the deploy controller,
``chip_smoke.py``'s parent) passes ``compile_counters=False``.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

# the checkout's own .env, found relative to the PACKAGE — never the CWD:
# a CLI started from a work directory, /tmp or a spawned replica's
# directory must run under the same LIBTPU_INIT_ARGS and compile-cache
# directory as one started from the repo root
REPO_ENV = Path(__file__).resolve().parents[2] / ".env"


def load_env_file(path: Optional[str] = None, *,
                  compile_counters: bool = True) -> dict:
    """Load KEY=VALUE pairs into os.environ (existing keys win). Returns
    the parsed mapping; missing file -> empty dict, like load_dotenv.
    ``path=None`` loads the checkout's ``.env`` (``REPO_ENV``)."""
    parsed = _load(Path(path) if path is not None else REPO_ENV)
    if compile_counters:
        from progen_tpu.telemetry import compiles

        compiles.install()
    return parsed


def _load(p: Path) -> dict:
    if not p.exists():
        return {}
    parsed = {}
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#") or "=" not in line:
            continue
        if line.startswith("export "):
            line = line[len("export ") :]
        key, _, value = line.partition("=")
        key, value = key.strip(), value.strip()
        if value and value[0] in "'\"":  # quoted: keep everything inside
            value = value.strip(value[0])
        else:  # unquoted: dotenv strips trailing inline comments
            value = value.split(" #", 1)[0].split("\t#", 1)[0].strip()
        # ${DOTENV_DIR} expands to the directory holding this .env file, so
        # a committed .env can point at repo-relative paths (e.g. the XLA
        # compilation cache) without baking in one machine's checkout path
        value = value.replace("${DOTENV_DIR}", str(p.parent.resolve()))
        parsed[key] = value
        os.environ.setdefault(key, value)
    return parsed
