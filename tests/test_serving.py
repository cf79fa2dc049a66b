"""Serving engine correctness: bit-parity with the standalone decoder.

The engine's whole value proposition is that continuous batching is
free of sampling-semantics drift — a request served from any slot, at
any admission time, next to any neighbors, must produce EXACTLY the
tokens ``sample_fast`` would have produced alone with the same key.
Every test here asserts token-for-token equality, not distributions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.sampling import sample_fast
from progen_tpu.serving import Request, Scheduler, ServeEngine

TINY = ProGenConfig(
    num_tokens=32,
    dim=32,
    seq_len=32,
    depth=2,
    window_size=8,
    global_mlp_depth=1,
    heads=2,
    dim_head=16,
    ff_mult=2,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    model = ProGen(TINY)
    tokens = jnp.zeros((1, TINY.seq_len), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    from flax.core import meta

    return model, meta.unbox(variables)["params"]


def _reference(model, params, req: Request) -> np.ndarray:
    key = req.key if req.key is not None else jax.random.PRNGKey(req.seed)
    return np.asarray(
        sample_fast(
            key, model, params, jnp.asarray(req.prime, jnp.int32),
            req.length, top_k=req.top_k, add_bos=req.add_bos,
            temperature=req.temperature, top_p=req.top_p,
        )
    )


def _mixed_requests(n):
    """n overlapping requests with mixed lengths AND mixed sampling
    params (the acceptance-criteria workload)."""
    rng = np.random.RandomState(7)
    knob_grid = [
        {},  # reference-parity defaults
        {"temperature": 0.7},
        {"top_p": 0.9},
        {"top_k": None},
        {"temperature": 1.3, "top_p": 0.8, "top_k": 5},
        {"top_k": 3},
        {"temperature": 0.5, "top_k": 10},
        {"add_bos": True},
    ]
    reqs = []
    for i in range(n):
        plen = int(rng.randint(1, 8))
        prime = rng.randint(1, TINY.num_tokens, size=plen)
        knobs = dict(knob_grid[i % len(knob_grid)])
        length = int(
            rng.randint(plen + 1 + knobs.get("add_bos", False) + 1, 30)
        )
        reqs.append(
            Request(
                id=f"r{i}", prime=prime, length=length,
                key=jax.random.PRNGKey(1000 + i), **knobs,
            )
        )
    return reqs


class TestEngineParity:
    def test_overlapping_mixed_requests_match_standalone(
        self, model_and_params
    ):
        """The acceptance-criteria integration test: >= 8 overlapping
        requests, mixed lengths and sampling params, through a pool
        SMALLER than the request count (forcing slot churn), submitted
        in two staggered waves (forcing mid-flight admission) — every
        completion must equal the standalone decode token-for-token."""
        model, params = model_and_params
        reqs = _mixed_requests(9)
        engine = ServeEngine(model, params, max_slots=3, max_len=32)
        sched = Scheduler(engine, max_queue=16)
        for req in reqs[:5]:
            ok, reason = sched.submit(req)
            assert ok, reason
        # advance a few iterations so the second wave joins mid-decode
        events, completions = [], []
        for _ in range(3):
            ev, comp = sched.step()
            events.extend(ev)
            completions.extend(comp)
        for req in reqs[5:]:
            ok, reason = sched.submit(req)
            assert ok, reason
        ev, comp = sched.run_to_completion(max_steps=2000)
        events.extend(ev)
        completions.extend(comp)

        assert len(completions) == len(reqs)
        by_id = {c.request_id: c for c in completions}
        for req in reqs:
            ref = _reference(model, params, req)
            got = by_id[req.id].tokens
            np.testing.assert_array_equal(
                got, ref,
                err_msg=f"{req.id} diverged from standalone decode",
            )
        # streamed tokens must agree with the completed buffers
        for req in reqs:
            ref = _reference(model, params, req)
            streamed = [e for e in events if e.request_id == req.id]
            for e in streamed[:-1]:  # final token may be truncated to 0
                assert ref[e.index] == e.token

    def test_slot_reuse_after_eos_is_bit_identical(self, model_and_params):
        """A request decoded in a RE-USED slot (prior occupant stopped at
        EOS, leaving its cache/state garbage at a different position)
        must match a fresh standalone decode exactly — the slot-reset
        guarantee the pool design leans on."""
        model, params = model_and_params
        # an early EOS by construction: the prime already carries the
        # second zero (BOS is the first), so the occupant stops at its
        # first decode step, primed deeper (8 positions) than the
        # follower will be (3)
        eos_req = Request(
            id="eos", prime=np.array([3, 5, 7, 11, 2, 6, 0]), length=30,
            add_bos=True, key=jax.random.PRNGKey(0),
        )
        nz = np.flatnonzero(_reference(model, params, eos_req) == 0)
        assert nz[1] == len(eos_req.prime)

        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=4)
        follow = Request(
            id="follow", prime=np.array([9, 2, 14]), length=28,
            temperature=0.8, top_p=0.95, key=jax.random.PRNGKey(777),
        )
        for req in (eos_req, follow):
            ok, reason = sched.submit(req)
            assert ok, reason
        _, completions = sched.run_to_completion(max_steps=500)
        by_id = {c.request_id: c for c in completions}
        # occupant really stopped at EOS (not max length): it generated
        # fewer tokens than requested
        ref_eos = _reference(model, params, eos_req)
        np.testing.assert_array_equal(by_id["eos"].tokens, ref_eos)
        start = len(eos_req.prime) + 1
        assert by_id["eos"].n_generated < eos_req.length - start
        # with one slot, "follow" necessarily reused it
        np.testing.assert_array_equal(
            by_id["follow"].tokens, _reference(model, params, follow)
        )

    def test_engine_matches_across_pool_sizes(self, model_and_params):
        """The same request through pools of different sizes (different
        compiled shapes, different neighbors) yields the same tokens —
        output depends only on (params, prime, key, knobs)."""
        model, params = model_and_params
        req = Request(
            id="x", prime=np.array([4, 8, 15]), length=24,
            key=jax.random.PRNGKey(5),
        )
        outs = []
        for slots in (1, 4):
            engine = ServeEngine(model, params, max_slots=slots, max_len=32)
            sched = Scheduler(engine, max_queue=4)
            ok, _ = sched.submit(req)
            assert ok
            _, comps = sched.run_to_completion(max_steps=300)
            outs.append(comps[0].tokens)
        np.testing.assert_array_equal(outs[0], outs[1])


class TestCompileOnce:
    def test_decode_step_compiles_once_per_engine_lifetime(
        self, model_and_params
    ):
        """Continuous batching on TPU is only viable if slot churn never
        retraces: across admissions, EOS exits, slot reuse, and every
        sampling-knob mix, the decode step and the prefill must each hit
        the jit cache after their first call."""
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=16)
        ok, _ = sched.submit(
            Request(id="warm", prime=np.array([1, 2]), length=8,
                    key=jax.random.PRNGKey(0))
        )
        assert ok
        sched.step()  # first decode step: the one allowed compile
        decode_after_first = ServeEngine.decode_compile_count()
        prefill_after_first = ServeEngine.prefill_compile_count()
        for req in _mixed_requests(6):
            ok, reason = sched.submit(req)
            assert ok, reason
        sched.run_to_completion(max_steps=2000)
        assert ServeEngine.decode_compile_count() == decode_after_first
        assert ServeEngine.prefill_compile_count() == prefill_after_first


class TestInt8Decode:
    """Int8 weight-only quantization (ops/quant.py + quantize_int8=True):
    scheme selectivity, calibration honesty, and the distributional
    closeness of the quantized decode path to full precision. The int8
    stream is NOT bit-identical to fp (that's the accuracy trade the
    calibration report quantifies), so these tests assert bounded
    divergence, not token equality."""

    def test_quantize_tree_targets_matmul_kernels_only(
        self, model_and_params
    ):
        from progen_tpu.ops.quant import quantize_tree

        _, params = model_and_params
        q_params, scales, report = quantize_tree(params)
        assert jax.tree_util.tree_structure(
            q_params
        ) == jax.tree_util.tree_structure(params)
        assert len(report) == len(scales) > 0
        for entry in report:
            assert entry["path"].endswith("'kernel']")
            assert len(entry["shape"]) == 2
            assert entry["bytes_int8"] < entry["bytes_fp"]
        quantized = {e["path"] for e in report}

        def check(path, fp_leaf):
            key = jax.tree_util.keystr(path)
            q_leaf = q_params
            for p in path:
                q_leaf = q_leaf[p.key]
            if key in quantized:
                assert q_leaf.dtype == jnp.int8
            else:  # embeddings, norms, biases, spatial mix: untouched
                assert q_leaf.dtype == fp_leaf.dtype

        jax.tree_util.tree_map_with_path(check, params)

    def test_dequantize_error_within_one_step(self, model_and_params):
        from progen_tpu.ops.quant import dequantize_tree, quantize_tree

        _, params = model_and_params
        q_params, scales, report = quantize_tree(params)
        deq = dequantize_tree(q_params, scales, jnp.float32)
        by_path = {e["path"]: e for e in report}

        def check(path, fp_leaf):
            key = jax.tree_util.keystr(path)
            if key not in by_path:
                return
            d_leaf = deq
            for p in path:
                d_leaf = d_leaf[p.key]
            err = float(
                jnp.max(jnp.abs(d_leaf - fp_leaf.astype(jnp.float32)))
            )
            # symmetric rounding: at most half an int8 step per channel
            amax = float(jnp.max(jnp.abs(fp_leaf)))
            assert err <= amax / 127.0 * 0.5 + 1e-6
            assert err == pytest.approx(
                by_path[key]["max_abs_err"], abs=1e-6
            )

        jax.tree_util.tree_map_with_path(check, params)

    def test_engine_calibration_report(self, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(
            model, params, max_slots=2, max_len=32, quantize_int8=True
        )
        rep = engine.quant_report
        assert rep is not None and rep["bits"] == 8
        assert rep["quantized_leaves"] == len(rep["leaves"]) > 0
        assert rep["bytes_int8"] < rep["bytes_fp"] / 2
        assert rep["weight_max_abs_err"] < 0.05
        assert rep["logits_max_abs_err"] < 1.0
        fp_engine = ServeEngine(model, params, max_slots=2, max_len=32)
        assert fp_engine.quant_report is None

    def test_teacher_forced_distribution_close(self, model_and_params):
        """Softmax total-variation distance between fp and dequantized
        params on a fixed prompt — the distributional check behind the
        per-token agreement the decode-int8 bench reports."""
        from progen_tpu.ops.quant import dequantize_tree, quantize_tree

        model, params = model_and_params
        q_params, scales, _ = quantize_tree(params)
        deq = dequantize_tree(q_params, scales, jnp.float32)
        prompt = [1, 7, 23, 4, 9, 2, 15, 30]
        tokens = jnp.array(
            [prompt * (TINY.seq_len // len(prompt))], jnp.int32
        )
        p = jax.nn.softmax(
            model.apply({"params": params}, tokens).astype(jnp.float32)
        )
        q = jax.nn.softmax(
            model.apply({"params": deq}, tokens).astype(jnp.float32)
        )
        tv = float(jnp.max(0.5 * jnp.sum(jnp.abs(p - q), axis=-1)))
        assert tv < 0.1

    def test_int8_decode_mostly_agrees_with_fp(self, model_and_params):
        model, params = model_and_params
        streams = {}
        for int8 in (False, True):
            engine = ServeEngine(
                model, params, max_slots=2, max_len=32,
                quantize_int8=int8,
            )
            sched = Scheduler(engine, max_queue=8)
            for i in range(2):
                ok, reason = sched.submit(Request(
                    id=f"r{i}", prime=np.array([1, 5 + i]), length=24,
                    key=jax.random.PRNGKey(42 + i),
                ))
                assert ok, reason
            _, done = sched.run_to_completion(max_steps=500)
            streams[int8] = {
                c.request_id: np.asarray(c.tokens) for c in done
            }
        agree = total = 0
        for rid, fp_toks in streams[False].items():
            q_toks = streams[True][rid]
            n = min(len(fp_toks), len(q_toks))
            agree += int((fp_toks[:n] == q_toks[:n]).sum())
            total += n
        assert total > 0
        assert agree / total >= 0.6

    def test_int8_programs_are_counted_by_the_compile_gauges(
        self, model_and_params
    ):
        """An int8 engine runs the programs every engine runs, so the
        two jit-cache counters see its compiles — and see none after the
        first decode step and the first admission, whatever the next
        request's length or chunk budget. (Shapes no other engine of
        this module has, so the first calls are real compiles.)"""
        model, params = model_and_params
        engine = ServeEngine(
            model, params, max_slots=3, max_len=27, quantize_int8=True
        )
        decode0 = engine.decode_compile_count()
        prefill0 = engine.prefill_compile_count()
        slot = engine.acquire()
        engine.prefill(slot, np.array([1, 5, 9]), 12,
                       key=jax.random.PRNGKey(0))
        assert engine.prefill_compile_count() > prefill0
        assert engine.decode_compile_count() == decode0
        engine.decode_step()
        assert engine.decode_compile_count() == decode0 + 1
        warm = (engine.decode_compile_count(),
                engine.prefill_compile_count())
        # another length, fed under another budget, beside the first
        pending = engine.begin_prefill(
            engine.acquire(), np.array([2, 4, 6, 8, 10, 12, 14, 3, 5]), 20,
            key=jax.random.PRNGKey(1),
        )
        while not engine.advance_prefill(pending, 3):
            engine.decode_step()
        engine.decode_step()
        assert (engine.decode_compile_count(),
                engine.prefill_compile_count()) == warm

