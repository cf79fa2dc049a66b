"""bench.py suite plumbing (pure-python parts — phases themselves run on
hardware via the driver; see bench.py docstring)."""

import importlib.util
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    return mod


class TestPhasePlumbing:
    def test_every_phase_resolvable(self, bench):
        # every scheduled phase must map to a runner + a recipe
        for name, timeout in bench._PHASES:
            assert timeout > 0
            if name.startswith("train-"):
                cfg = name[len("train-"):]
                cfg = (cfg.removesuffix("-pallas").removesuffix("-xla")
                       .removesuffix("-bs32").removesuffix("-scan"))
                assert cfg in bench._RECIPES, name
                assert (REPO / "configs" / "model" / f"{cfg}.toml").exists()
            elif name.startswith("kernel-w"):
                spec = name[len("kernel-w"):].split("-n")
                assert int(spec[0]) in (256, 512)
                if len(spec) > 1:  # shape variant rides a real config's n
                    assert int(spec[1]) in (2048, 4096, 8192)

    def test_unknown_phase_raises(self, bench):
        with pytest.raises(ValueError):
            bench.run_phase("nope")

    def test_prior_round_ignores_cpu_fallback(self, bench, monkeypatch,
                                              tmp_path):
        import json

        # the cpu-fallback rounds the driver recorded before the fallback
        # was removed (r01-r05): the baseline chain must stay unpolluted.
        # Hermetic on purpose — the live repo's BENCH_r*.json are driver
        # artifacts.
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(
            {"n": 1, "rc": 1, "parsed": None}
        ))
        (tmp_path / "BENCH_r02.json").write_text(json.dumps({
            "parsed": {
                "metric": "cpu_fallback_smoke_tokens_per_sec",
                "value": 40593.3, "platform": "cpu",
            }
        }))
        monkeypatch.setattr(bench, "_REPO", tmp_path)
        assert bench._prior_round_value() is None

    def test_kernel_combo_pricing(self, bench):
        # plain XLA wins when no mix beats the fused autodiff pipeline
        assert bench._price_kernel_combos(
            {"xla": 1.0, "pallas_g1": 1.2}, {"kv": 0.9}, 1.8,
        ) == ("xla", "xla", "xla")
        # xla fwd + pallas bwd: priced with t_xf, not the pallas fwd
        assert bench._price_kernel_combos(
            {"xla": 1.0, "pallas_g1": 1.2}, {"kv": 0.5}, 1.8,
        ) == ("xla", "xla", "kv")
        # g-batched fwd + pallas bwd
        assert bench._price_kernel_combos(
            {"xla": 1.0, "pallas_g1": 0.9, "pallas_g4": 0.6},
            {"kv": 0.5, "halo": 0.7}, 1.8,
        ) == ("pallas_g4", "pallas", "kv")

    def test_kernel_combo_pricing_near_tie_not_greedy(self, bench):
        # ADVICE r4: a marginally-faster pallas forward must NOT drag the
        # policy onto a combo whose TOTAL loses to plain XLA — the greedy
        # fwd-then-bwd pick would ship (pallas_g4, xla) here, paying its
        # forward twice (0.95 + 1.3 = 2.25) vs plain XLA's 1.3
        assert bench._price_kernel_combos(
            {"xla": 1.0, "pallas_g1": 1.1, "pallas_g4": 0.95},
            {"kv": 0.8}, 1.3,
        ) == ("xla", "xla", "xla")

    def test_prior_round_ignores_carried_tpu_record(
            self, bench, monkeypatch, tmp_path):
        import json

        # an old fallback round quoting an archived TPU number is not a
        # measurement of that round: only a round whose OWN metric ran on
        # the chip sets the baseline
        (tmp_path / "BENCH_r03.json").write_text(json.dumps({
            "parsed": {
                "metric": "cpu_fallback_smoke_tokens_per_sec",
                "value": 33000.0, "platform": "cpu",
                "last_tpu_record": {"value": 206369.0},
            }
        }))
        (tmp_path / "BENCH_r04.json").write_text(json.dumps({
            "parsed": {
                "metric": "train_tokens_per_sec_per_chip",
                "value": 180000.0, "platform": "tpu",
            }
        }))
        monkeypatch.setattr(bench, "_REPO", tmp_path)
        assert bench._prior_round_value() == 180000.0

    def test_train_phase_off_chip_is_an_error_not_a_number(self, bench):
        # the suite runs on the CPU platform: a train phase must refuse
        # to report tokens/s/chip or MFU here, before building anything
        with pytest.raises(RuntimeError, match="need a TPU"):
            bench._train_bench("tiny")

    def test_suspect_fields_without_a_peak(self, bench):
        # CPU smoke of a kernel phase: no peak, so no implied-device-FLOP/s
        assert bench._suspect_fields(1e12, 1.0, None) == {
            "timing_suspect": False
        }
        assert bench._suspect_fields(1e15, 1.0, 197e12)["timing_suspect"]

    def test_large_projection_math(self, bench):
        res = bench._large_projection()
        assert res["num_params"] > 1.2e9  # the 1.2B BASELINE.md config
        assert not res["hbm_fit_single_chip"]  # 16 B/param > 16 GB HBM
        # per-chip share at model=8 must fit v5e HBM with room for
        # activations
        assert res["per_chip_state_gb_at_model8"] < 8

    def test_config_loader_defaults_bf16(self, bench):
        cfg = bench._load_config("tiny")
        assert cfg.dtype == "bfloat16"
        cfg = bench._load_config("long8k")
        assert cfg.use_pallas_attn  # enabled in the shipped TOML
        cfg = bench._load_config("long8k", use_pallas_attn=False)
        assert not cfg.use_pallas_attn


class TestOrchestrator:
    """main()'s TPU-suite control flow — the driver runs this blind on
    hardware, so the headline-flush/budget/summary logic is pinned here
    with stubbed phases (no chip, no subprocesses)."""

    def _run_main(self, bench, monkeypatch, tmp_path, capsys,
                  phase_results, budget="3000"):
        monkeypatch.setattr(bench, "_probe_platform", lambda *a, **k: "tpu")
        # keep the stubbed control-flow tests hermetic: the in-parent
        # host-side phase writes real tempfiles and builds the C++ engine
        monkeypatch.setattr(
            bench, "_data_io_safe",
            lambda: {"phase": "data-io", "host_side": True,
                     "native_speedup": 3.4, "parse_py_mb_s": 60.0,
                     "platform": "host"},
        )
        # pin the baseline chain: the real repo grows BENCH_r*.json TPU
        # records across rounds, and vs_baseline must stay test-controlled
        monkeypatch.setattr(bench, "_prior_round_value", lambda: None)
        monkeypatch.setattr(bench, "_DETAIL_PATH",
                            tmp_path / "bench_detail.json")
        monkeypatch.setattr(
            bench, "_run_phase_subprocess",
            lambda name, timeout: phase_results[name],
        )
        monkeypatch.setattr(
            bench, "_PHASES",
            tuple((n, 60) for n in phase_results),
        )
        monkeypatch.setenv("BENCH_BUDGET_SEC", budget)
        self.rc = bench.main()
        return capsys.readouterr().out.strip().splitlines()

    def test_headline_flushed_then_rich_summary(self, bench, monkeypatch,
                                                tmp_path, capsys):
        import json

        tiny = {
            "phase": "train-tiny", "config": "tiny",
            "tokens_per_sec_per_chip": 100000.0, "mfu": 0.42,
            "step_ms": 160.0, "compile_s": 30.0, "num_params": 38000000,
            "batch": "4x4x1024", "dtype": "bfloat16",
            "use_pallas_attn": False, "loss": 5.5, "chips": 1,
            "platform": "tpu",
        }
        kern = {
            "phase": "kernel-w256", "fwd_speedup": 1.4, "bwd_speedup": 1.2,
            "fwd_ms": {}, "bwd_ms": {}, "platform": "tpu",
        }
        lines = self._run_main(
            bench, monkeypatch, tmp_path, capsys,
            {"train-tiny": tiny, "kernel-w256": kern},
        )
        assert self.rc == 0
        payloads = [json.loads(line) for line in lines if line.startswith("{")]
        assert len(payloads) == 2  # early headline + final rich line
        head, final = payloads
        assert head["metric"] == "train_tokens_per_sec_per_chip"
        assert head["value"] == 100000.0 and head["platform"] == "tpu"
        # no prior TPU rounds: the value establishes the baseline
        assert head["vs_baseline"] == 1.0
        assert final["value"] == head["value"]
        assert final["suite"]["kernel-w256"]["fwd_speedup"] == 1.4
        detail = json.loads((tmp_path / "bench_detail.json").read_text())
        assert detail["platform"] == "tpu"
        # stubbed phases + the in-parent host-side and projection studies
        assert [p["phase"] for p in detail["phases"]] == [
            "train-tiny", "kernel-w256", "data-io", "large-projection",
        ]
        assert final["suite"]["data-io"]["native_speedup"] == 3.4

    def test_non_tpu_phase_result_recorded_as_error(self, bench,
                                                    monkeypatch, tmp_path,
                                                    capsys):
        import json

        tiny = {
            "phase": "train-tiny", "config": "tiny",
            "tokens_per_sec_per_chip": 1.0, "mfu": 0.0, "step_ms": 1.0,
            "compile_s": 1.0, "num_params": 1, "batch": "x",
            "dtype": "bfloat16", "use_pallas_attn": False, "loss": 1.0,
            "chips": 1, "platform": "tpu",
        }
        rogue = {"phase": "kernel-w256", "platform": "cpu",
                 "fwd_speedup": 9.9, "bwd_speedup": 9.9}
        self._run_main(
            bench, monkeypatch, tmp_path, capsys,
            {"train-tiny": tiny, "kernel-w256": rogue},
        )
        detail = json.loads((tmp_path / "bench_detail.json").read_text())
        kern = [p for p in detail["phases"] if p["phase"] == "kernel-w256"]
        assert "error" in kern[0]  # a CPU result never masquerades as TPU

    def test_no_chip_no_metric_nonzero_exit(self, bench, monkeypatch,
                                            tmp_path, capsys):
        # the probe finds a CPU (or nothing): no phase runs, nothing is
        # printed on stdout, nothing is written, and the exit is non-zero
        for found in ("cpu", None):
            monkeypatch.setattr(
                bench, "_probe_platform", lambda *a, f=found, **k: f
            )
            monkeypatch.setattr(
                bench, "_run_phase_subprocess",
                lambda *a, **k: pytest.fail("no phase may run off-chip"),
            )
            monkeypatch.setattr(bench, "_DETAIL_PATH",
                                tmp_path / "bench_detail.json")
            assert bench.main() == 1
            captured = capsys.readouterr()
            assert captured.out == ""
            assert "No chip, no number" in captured.err
            assert not (tmp_path / "bench_detail.json").exists()

    def test_failed_headline_no_metric_nonzero_exit(self, bench,
                                                    monkeypatch, tmp_path,
                                                    capsys):
        import json

        kern = {
            "phase": "kernel-w256", "fwd_speedup": 1.4, "bwd_speedup": 1.2,
            "fwd_ms": {}, "bwd_ms": {}, "platform": "tpu",
        }
        lines = self._run_main(
            bench, monkeypatch, tmp_path, capsys,
            {"train-tiny": {"phase": "train-tiny",
                            "error": "timeout after 720s"},
             "kernel-w256": kern},
        )
        # no CPU smoke stands in for the headline: no JSON line at all
        assert self.rc == 1
        assert not [ln for ln in lines if ln.startswith("{")]
        # the phases that did run are still on record for the post-mortem
        detail = json.loads((tmp_path / "bench_detail.json").read_text())
        assert [p["phase"] for p in detail["phases"]][:2] == [
            "train-tiny", "kernel-w256",
        ]

    def test_parent_stays_off_jax(self, bench, monkeypatch, tmp_path,
                                  capsys):
        """A chip belongs to one process: the orchestrating parent must
        not initialise a backend before (or while) its phase children
        run — run it in a fresh interpreter and look."""
        import subprocess
        import textwrap

        script = textwrap.dedent("""
            import importlib.util, sys
            spec = importlib.util.spec_from_file_location("bench", sys.argv[1])
            bench = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(bench)
            bench._probe_platform = lambda *a, **k: "tpu"
            bench._run_phase_subprocess = lambda name, timeout: {
                "phase": name, "error": "stub"}
            bench._data_io_safe = lambda: {"phase": "data-io"}
            bench._PHASES = (("train-tiny", 60),)
            from pathlib import Path
            bench._DETAIL_PATH = Path(sys.argv[2]) / "bench_detail.json"
            bench._LOG_DIR = Path(sys.argv[2]) / "bench_logs"
            rc = bench.main()
            if "jax" in sys.modules:
                from jax._src import xla_bridge
                assert not xla_bridge.backends_are_initialized()
            print("parent off jax, rc", rc)
        """)
        out = subprocess.run(
            [sys.executable, "-c", script, str(REPO / "bench.py"),
             str(tmp_path)],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert "parent off jax, rc 1" in out.stdout


class TestDetailRecord:
    """Per-phase results land under runs/ — a bench run never writes into
    a tracked file."""

    def test_detail_and_measured_policy_live_under_runs(self, bench):
        for path in (bench._DETAIL_PATH, bench._MEASURED_POLICY_PATH):
            assert path.parent == REPO / "runs"
        assert "runs/" in (REPO / ".gitignore").read_text().split()

    def test_write_detail_creates_parent_and_overwrites(self, bench,
                                                        monkeypatch,
                                                        tmp_path):
        import json

        path = tmp_path / "runs" / "bench_detail.json"
        monkeypatch.setattr(bench, "_DETAIL_PATH", path)
        bench._write_detail({"platform": "tpu", "phases": [1]})
        bench._write_detail({"platform": "tpu", "phases": [1, 2]})
        assert json.loads(path.read_text())["phases"] == [1, 2]

    def test_in_process_entry_points_require_the_chip(self, bench):
        # `bench.py kernel` / `--config X` on the CPU platform: non-zero
        # exit, no metric
        with pytest.raises(SystemExit) as exc:
            bench._require_tpu()
        assert "needs a TPU" in str(exc.value)
        with pytest.raises(SystemExit):
            bench.kernel_main()


class TestBenchGate:
    """The ratchet over the BENCH_r0N.json trajectory
    (progen_tpu/utils/bench_gate + the `bench.py gate` subcommand
    tier1.yml enforces)."""

    def _write(self, tmp_path, rnd, parsed):
        import json

        (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(
            json.dumps({"n": rnd, "parsed": parsed})
        )

    def _cpu_round(self, value, **extra):
        return {"metric": "cpu_fallback_smoke_tokens_per_sec",
                "value": value, "platform": "cpu", **extra}

    def test_best_prior_is_max_not_latest(self, tmp_path):
        from progen_tpu.utils.bench_gate import best_prior, load_trajectory

        self._write(tmp_path, 1, None)  # torn round: kept, skipped
        self._write(tmp_path, 2, self._cpu_round(40000.0))
        self._write(tmp_path, 3, self._cpu_round(27000.0))
        best = best_prior(load_trajectory(tmp_path), "cpu")
        assert best["value"] == 40000.0 and best["round"] == 2

    def test_tpu_chain_reads_carried_records(self, tmp_path):
        # bench_gate still reads the carries old driver rounds hold
        from progen_tpu.utils.bench_gate import best_prior, load_trajectory

        self._write(tmp_path, 2, {
            "metric": "train_tokens_per_sec_per_chip",
            "value": 180000.0, "platform": "tpu",
        })
        self._write(tmp_path, 3, self._cpu_round(
            27000.0, last_tpu_record={"value": 206369.0}
        ))
        records = load_trajectory(tmp_path)
        best = best_prior(records, "tpu")
        assert best["value"] == 206369.0 and best["carried"]
        # auto prefers the tpu chain over the cpu one
        assert best_prior(records, "auto")["metric"] == "tpu"

    def test_cpu_chain_never_reads_tpu_records(self, tmp_path):
        from progen_tpu.utils.bench_gate import best_prior, load_trajectory

        self._write(tmp_path, 2, {
            "metric": "train_tokens_per_sec_per_chip",
            "value": 180000.0, "platform": "tpu",
        })
        assert best_prior(load_trajectory(tmp_path), "cpu") is None

    def test_evaluate_gate_ratchet(self):
        from progen_tpu.utils.bench_gate import evaluate_gate

        best = {"metric": "cpu", "value": 1000.0, "round": 2,
                "carried": False}
        assert evaluate_gate(900.0, best, 0.2)["ok"]
        assert not evaluate_gate(700.0, best, 0.2)["ok"]
        assert evaluate_gate(1.0, None, 0.2)["ok"]  # first round: sets bar
        with pytest.raises(ValueError):
            evaluate_gate(900.0, best, 1.5)

    def test_unknown_metric_raises(self):
        from progen_tpu.utils.bench_gate import best_prior

        with pytest.raises(ValueError):
            best_prior([], "mfu")

    def test_serve_chains_ratchet_and_stay_separate(self, tmp_path):
        """The two serving ratios are independent gate chains: each
        reads its own headline rounds plus the direct-key carry on
        rounds whose headline is a train/cpu number, and neither leaks
        into the cpu/tpu chains."""
        from progen_tpu.utils.bench_gate import best_prior, load_trajectory

        self._write(tmp_path, 2, {
            "metric": "serve_admit_stall_ratio", "value": 1.8,
            "prefix_cache_speedup": 6.0, "platform": "cpu",
        })
        self._write(tmp_path, 3, self._cpu_round(
            27000.0,
            serve_admit_stall_ratio=2.3,
            serve_prefix_cache_speedup=9.0,
        ))
        records = load_trajectory(tmp_path)
        best = best_prior(records, "serve_admit_stall_ratio")
        assert best["value"] == 2.3 and best["carried"]
        best = best_prior(records, "serve_prefix_cache_speedup")
        assert best["value"] == 9.0 and best["round"] == 3
        # the serving rounds never pollute the throughput chains
        assert best_prior(records, "cpu")["value"] == 27000.0
        assert best_prior(records, "tpu") is None

    def test_gate_cli_from_json_key(self, bench, monkeypatch, tmp_path,
                                    capsys):
        """``--from-json-key`` reads the second gated number out of the
        decode-admit-stall phase JSON."""
        import json

        monkeypatch.setattr(bench, "_REPO", tmp_path)
        phase = tmp_path / "admit.json"
        phase.write_text(json.dumps({
            "phase": "decode-admit-stall",
            "metric": "serve_admit_stall_ratio",
            "value": 2.1, "prefix_cache_speedup": 7.5,
        }))
        assert bench.gate_main([
            "--metric", "serve_prefix_cache_speedup",
            "--from-json", str(phase),
            "--from-json-key", "prefix_cache_speedup",
        ]) == 0
        assert bench.gate_main([
            "--metric", "serve_admit_stall_ratio",
            "--from-json", str(phase),
        ]) == 0
        assert bench.gate_main([
            "--metric", "serve_admit_stall_ratio",
            "--from-json", str(phase),
            "--from-json-key", "no_such_key",
        ]) == 2
        capsys.readouterr()

    def test_gate_cli_exit_codes(self, bench, monkeypatch, tmp_path,
                                 capsys):
        self._write(tmp_path, 2, self._cpu_round(1000.0))
        monkeypatch.setattr(bench, "_REPO", tmp_path)
        args = ["--metric", "cpu", "--tolerance", "0.2"]
        assert bench.gate_main(args + ["--value", "900"]) == 0
        assert bench.gate_main(args + ["--value", "100"]) == 1
        assert bench.gate_main(
            args + ["--from-json", str(tmp_path / "missing.json")]
        ) == 2
        # the gate measures nothing itself: no value source is a usage
        # error, not a fresh CPU smoke
        assert bench.gate_main(args) == 2
        capsys.readouterr()

    def test_gate_cli_from_json_forms(self, bench, monkeypatch, tmp_path,
                                      capsys):
        import json

        self._write(tmp_path, 2, self._cpu_round(1000.0))
        monkeypatch.setattr(bench, "_REPO", tmp_path)
        bare = tmp_path / "phase.json"
        bare.write_text(json.dumps({"value": 950.0}))
        wrapped = tmp_path / "headline.json"
        wrapped.write_text(json.dumps({"parsed": {"value": 100.0}}))
        args = ["--metric", "cpu", "--tolerance", "0.2", "--from-json"]
        assert bench.gate_main(args + [str(bare)]) == 0
        assert bench.gate_main(args + [str(wrapped)]) == 1
        capsys.readouterr()


class TestFusedPhaseDispatch:
    def test_kernel_fused_parses_block(self, bench, monkeypatch):
        calls = []

        def fake(block):
            calls.append(block)
            return {"phase": f"kernel-fused-w{block}"}

        monkeypatch.setattr(bench, "_fused_kernel_bench", fake)
        bench.run_phase("kernel-fused-w256")
        bench.run_phase("kernel-fused-w512")
        assert calls == [256, 512]

    def test_decode_int8_dispatches(self, bench, monkeypatch):
        def fake():
            return {"phase": "decode-int8"}

        monkeypatch.setattr(bench, "_decode_int8_bench", fake)
        assert bench.run_phase("decode-int8")["phase"] == "decode-int8"

    def test_new_phases_scheduled_with_timeouts(self, bench):
        names = dict(bench._PHASES)
        assert names["kernel-fused-w256"] > 0
        assert names["kernel-fused-w512"] > 0
        assert names["decode-int8"] > 0
        assert names["decode-admit-stall"] > 0

    def test_decode_admit_stall_dispatches(self, bench, monkeypatch):
        def fake():
            return {"phase": "decode-admit-stall"}

        monkeypatch.setattr(bench, "_decode_admit_stall_bench", fake)
        res = bench.run_phase("decode-admit-stall")
        assert res["phase"] == "decode-admit-stall"
