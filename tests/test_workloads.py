"""Protein-design workload tests (progen_tpu/workloads/).

The acceptance contracts, each against an independent oracle:

  * shared scorer — ``cross_entropy`` and the batch scorer both reduce
    ``sequence_scores``; a scorer JSONL record's NLL/logprobs are
    bit-exact against a plain jitted forward at the same batch shape;
  * batch scoring is resumable — kill (or stop) mid-run, re-run, and
    the union of output shards holds every input id exactly once (the
    subprocess case drives the real CLI with PROGEN_CHAOS SIGKILL);
  * the vmapped mutagenesis scan matches a per-mutant loop reference;
  * infilled samples preserve frozen positions exactly, an all-free
    mask is bit-identical to unconstrained sampling under the same key,
    and the serving engine's constrained slots match ``sample_fast``;
  * embeddings: engine/scheduler answers equal a direct ``embed_step``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen

REPO = Path(__file__).resolve().parents[1]

# raw-id config: vocab 32 < any byte token, so infill tests speak ids
TINY = ProGenConfig(
    num_tokens=32, dim=32, seq_len=32, depth=2, window_size=8,
    global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2, dtype="float32",
)
# byte-vocab twin: scoring/mutagenesis tests feed real protein strings
BYTE_CFG = ProGenConfig(
    num_tokens=256, dim=32, seq_len=32, depth=2, window_size=8,
    global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2, dtype="float32",
)


def _init(config):
    from flax.core import meta

    model = ProGen(config)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, config.seq_len), jnp.int32)
    )
    return model, meta.unbox(variables)["params"]


@pytest.fixture(scope="module")
def tiny():
    return _init(TINY)


@pytest.fixture(scope="module")
def byte_model():
    return _init(BYTE_CFG)


def _aa_seq(rng, n):
    from progen_tpu.workloads import AA_ALPHABET

    return "".join(rng.choice(np.array(list(AA_ALPHABET)), size=n))


class TestInfillHost:
    def test_parse_template_roundtrip(self):
        from progen_tpu.workloads import parse_template

        tokens, frozen = parse_template("MK?LV??G")
        assert frozen == [True, True, False, True, True, False, False, True]
        assert tokens[2] == 0 and tokens[5] == 0 and tokens[6] == 0
        assert tokens[0] == ord("M") + 1 and tokens[-1] == ord("G") + 1

    def test_parse_template_custom_free_char(self):
        from progen_tpu.workloads import parse_template

        tokens, frozen = parse_template("A_C", "_")
        assert frozen == [True, False, True]

    def test_parse_template_errors(self):
        from progen_tpu.workloads import parse_template

        with pytest.raises(ValueError):
            parse_template("")
        with pytest.raises(ValueError):
            parse_template("MKLV")  # no free positions
        with pytest.raises(ValueError):
            parse_template("M?", free_char="??")

    def test_request_arrays_hoist_frozen_prefix(self):
        from progen_tpu.workloads import infill_request_arrays, parse_template

        tokens, frozen = parse_template("MK?LV??G")
        prime, length, tpl, frz = infill_request_arrays(tokens, frozen)
        assert list(prime) == [ord("M") + 1, ord("K") + 1]
        assert length == 9  # 8 template positions + BOS column
        # buffer coordinates: index 0 is BOS (free), template shifted by 1
        assert not frz[0] and list(tpl[1:]) == tokens
        assert list(frz[1:]) == frozen

    def test_request_arrays_leading_free_needs_bos(self):
        from progen_tpu.workloads import infill_request_arrays, parse_template

        tokens, frozen = parse_template("?KL")
        with pytest.raises(ValueError):
            infill_request_arrays(tokens, frozen, add_bos=False)
        prime, length, _, _ = infill_request_arrays(tokens, frozen)
        assert len(prime) == 0 and length == 4


class TestInfillSampling:
    def _constraint(self, length):
        # raw-id template: pin three interior positions, leave the rest
        # free (ids < TINY.num_tokens; 0 marks free slots)
        tpl = np.zeros((length,), np.int32)
        frz = np.zeros((length,), bool)
        for pos, tok in ((5, 7), (9, 3), (20, 11)):
            tpl[pos], frz[pos] = tok, True
        return tpl, frz

    def test_sample_preserves_frozen_positions(self, tiny):
        from progen_tpu.sampling import sample

        model, params = tiny
        length = TINY.seq_len  # the naive path's gMLP SGU constraint
        tpl, frz = self._constraint(length)
        out = np.asarray(sample(
            jax.random.PRNGKey(1), model, params,
            jnp.array([4, 2], jnp.int32), length, top_k=5, add_bos=True,
            template=jnp.asarray(tpl), frozen=jnp.asarray(frz),
        ))
        np.testing.assert_array_equal(out[frz], tpl[frz])

    def test_sample_fast_preserves_frozen_positions(self, tiny):
        from progen_tpu.sampling import sample_fast

        model, params = tiny
        length = 24
        tpl, frz = self._constraint(length)
        out = np.asarray(sample_fast(
            jax.random.PRNGKey(1), model, params,
            jnp.array([4, 2], jnp.int32), length, top_k=5, add_bos=True,
            template=jnp.asarray(tpl), frozen=jnp.asarray(frz),
        ))
        np.testing.assert_array_equal(out[frz], tpl[frz])
        # free positions stay in-vocab and nonzero before the stop rule
        assert (out >= 0).all() and (out < TINY.num_tokens).all()

    @pytest.mark.parametrize("fast", [False, True])
    def test_all_free_mask_equals_unconstrained(self, tiny, fast):
        from progen_tpu.sampling import sample, sample_fast

        model, params = tiny
        fn = sample_fast if fast else sample
        length = 24 if fast else TINY.seq_len
        prime = jnp.array([4, 2, 9], jnp.int32)
        plain = fn(jax.random.PRNGKey(3), model, params, prime, length,
                   top_k=5, add_bos=True)
        infill = fn(jax.random.PRNGKey(3), model, params, prime, length,
                    top_k=5, add_bos=True,
                    template=jnp.zeros((length,), jnp.int32),
                    frozen=jnp.zeros((length,), bool))
        # the constraint draws nothing extra: all-free is bit-identical
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(infill))

    def test_validation_errors(self, tiny):
        from progen_tpu.sampling import sample_fast

        model, params = tiny
        prime = jnp.array([4], jnp.int32)
        with pytest.raises(ValueError):  # template without frozen
            sample_fast(jax.random.PRNGKey(0), model, params, prime, 16,
                        template=jnp.zeros((16,), jnp.int32))
        with pytest.raises(ValueError):  # wrong shape
            sample_fast(jax.random.PRNGKey(0), model, params, prime, 16,
                        template=jnp.zeros((8,), jnp.int32),
                        frozen=jnp.zeros((8,), bool))
        with pytest.raises(ValueError):  # frozen position pinning id 0
            sample_fast(jax.random.PRNGKey(0), model, params, prime, 16,
                        template=jnp.zeros((16,), jnp.int32),
                        frozen=jnp.ones((16,), bool))


class TestInfillServing:
    def test_scheduler_matches_sample_fast(self, tiny):
        from progen_tpu.sampling import sample_fast
        from progen_tpu.serving import Request, Scheduler, ServeEngine

        model, params = tiny
        length = 24
        tpl = np.zeros((length,), np.int32)
        frz = np.zeros((length,), bool)
        tpl[6], frz[6] = 4, True
        tpl[15], frz[15] = 9, True
        engine = ServeEngine(model, params, max_slots=2, max_len=length)
        sched = Scheduler(engine)
        prime = np.array([4, 2], np.int32)
        ok, _ = sched.submit(Request(
            id="gen1", prime=prime, length=length, top_k=5, add_bos=True,
            seed=7, template=tpl, frozen=frz,
        ))
        assert ok
        done = {}
        for _ in range(length + 4):
            _, comps = sched.step()
            done.update({c.request_id: c for c in comps})
            if not sched.has_work:
                break
        ref = np.asarray(sample_fast(
            jax.random.PRNGKey(7), model, params, jnp.asarray(prime),
            length, top_k=5, add_bos=True,
            template=jnp.asarray(tpl), frozen=jnp.asarray(frz),
        ))
        np.testing.assert_array_equal(done["gen1"].tokens, ref)
        assert done["gen1"].tokens[6] == 4 and done["gen1"].tokens[15] == 9

    def test_journal_roundtrips_kind_and_constraint(self, tmp_path):
        from progen_tpu.serving import Request
        from progen_tpu.serving.journal import (
            RequestJournal,
            _classify,
            _read_state,
            resume_request,
        )

        path = str(tmp_path / "journal.jsonl")
        j = RequestJournal(path)
        tpl = np.array([0, 4, 0, 9], np.int32)
        frz = np.array([False, True, False, True], bool)
        j.accept(Request(
            id="g2", prime=np.array([4], np.int32), length=4, add_bos=True,
            key=jnp.asarray([1, 2], jnp.uint32), template=tpl, frozen=frz,
        ))
        j.accept(Request(
            id="e2", prime=np.array([4, 2], np.int32), length=3,
            add_bos=True, key=jnp.asarray([3, 4], jnp.uint32), kind="embed",
        ))
        j.close()
        state = _read_state(path)
        cls_g = _classify(state["g2"])
        cls_e = _classify(state["e2"])
        # an embed accept never mis-settles as "finished" (it emits no
        # tokens, so start >= length would otherwise claim completion)
        assert cls_e["kind"] == "pending"
        req_g = resume_request("g2", cls_g)
        req_e = resume_request("e2", cls_e)
        assert req_g.kind == "generate" and req_e.kind == "embed"
        np.testing.assert_array_equal(req_g.template, tpl)
        np.testing.assert_array_equal(req_g.frozen, frz)
        assert req_e.template is None


class TestEmbeddings:
    def test_embed_step_shape_and_mask(self, tiny):
        from progen_tpu.workloads import embed_step

        model, params = tiny
        row = np.zeros((2, TINY.seq_len), np.int32)
        row[0, :5] = [4, 2, 9, 11, 3]
        row[1, :5] = [4, 2, 9, 11, 3]
        row[1, 5:9] = [7, 7, 7, 7]
        out = np.asarray(embed_step(model, params, jnp.asarray(row)))
        assert out.shape == (2, TINY.dim) and out.dtype == np.float32
        # pooling masks pad: rows with different real tokens must differ
        assert not np.allclose(out[0], out[1])

    def test_engine_embed_matches_embed_step(self, tiny):
        from progen_tpu.serving import ServeEngine
        from progen_tpu.workloads import embed_step

        model, params = tiny
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        prime = np.array([4, 2, 9, 11], np.int32)
        vec = engine.embed(prime, add_bos=True)
        assert vec.shape == (TINY.dim,) and vec.dtype == np.float32
        # oracle: the same padded row through embed_step directly (the
        # engine buckets to >= window_size, full seq_len under gMLP)
        row = np.zeros((1, TINY.seq_len), np.int32)
        row[0, 1:1 + len(prime)] = prime
        ref = np.asarray(
            embed_step(engine._embed_model, params, jnp.asarray(row))
        )[0]
        np.testing.assert_array_equal(vec, ref)

    def test_scheduler_embed_request(self, tiny):
        from progen_tpu.serving import Request, Scheduler, ServeEngine

        model, params = tiny
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        sched = Scheduler(engine)
        ok, _ = sched.submit(Request(
            id="e1", prime=np.array([4, 2, 9], np.int32), length=8,
            add_bos=True, kind="embed",
        ))
        assert ok
        _, comps = sched.step()
        byid = {c.request_id: c for c in comps}
        assert "e1" in byid
        c = byid["e1"]
        assert c.embedding is not None and c.embedding.shape == (TINY.dim,)
        assert c.n_generated == 0 and not sched.has_work
        ref = engine.embed(np.array([4, 2, 9], np.int32), add_bos=True)
        np.testing.assert_array_equal(c.embedding, ref)

    def test_embed_rejects_oversized_prime(self, tiny):
        from progen_tpu.serving import Request, Scheduler, ServeEngine

        model, params = tiny
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        sched = Scheduler(engine)
        ok, reason = sched.submit(Request(
            id="e9", prime=np.zeros((TINY.seq_len + 4,), np.int32),
            length=8, kind="embed",
        ))
        assert not ok and reason


class TestSharedScorer:
    def test_cross_entropy_is_sequence_scores_head(self):
        from progen_tpu.training.loss import cross_entropy, sequence_scores

        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(3, 16, 32)).astype(np.float32))
        targets = jnp.asarray(rng.integers(0, 32, size=(3, 16)))
        np.testing.assert_array_equal(
            np.asarray(cross_entropy(logits, targets)),
            np.asarray(sequence_scores(logits, targets)[0]),
        )

    def test_scorer_jsonl_bit_exact_vs_plain_forward(self, byte_model,
                                                     tmp_path):
        from progen_tpu.data.dataset import collate
        from progen_tpu.training.loss import sequence_scores
        from progen_tpu.workloads import run_batch_score

        model, params = byte_model
        rng = np.random.default_rng(1)
        records = [
            (f"s{i}", ("# " + _aa_seq(rng, int(rng.integers(8, 24))))
             .encode("utf-8"))
            for i in range(8)
        ]
        out_dir = str(tmp_path / "scores")
        summary = run_batch_score(
            model, params, list(records), out_dir,
            batch_size=4, logprobs=True, resume=False,
        )
        assert summary["n_scored"] == 8 and summary["n_skipped"] == 0
        by_id = {}
        for shard in sorted(Path(out_dir).glob("scores-*.jsonl")):
            for line in shard.read_text().splitlines():
                rec = json.loads(line)
                by_id[rec["id"]] = rec

        # oracle: a JITTED plain forward at the SAME batch shape (XLA
        # fuses differently across batch shapes and jit boundaries, so
        # bit-exactness is only defined at matched shape + jit)
        @jax.jit
        def ref(params, data):
            ids, labels = data[..., :-1], data[..., 1:]
            logits = model.apply({"params": params}, ids)
            return sequence_scores(logits, labels)

        # gMLP fixes the bucket at seq_len, so batches are records in
        # arrival order, 4 at a time
        for b in range(2):
            chunk = records[4 * b:4 * b + 4]
            data = collate([raw for _, raw in chunk], BYTE_CFG.seq_len)
            nll, lp, mask = (np.asarray(x) for x in ref(params, data))
            for i, (rid, _) in enumerate(chunk):
                rec = by_id[rid]
                assert rec["nll"] == float(nll[i])  # bit-exact
                np.testing.assert_array_equal(
                    np.asarray(rec["logprobs"], np.float32),
                    lp[i][mask[i]].astype(np.float32),
                )

    def test_skips_too_long_records(self, byte_model, tmp_path):
        from progen_tpu.workloads import run_batch_score

        model, params = byte_model
        rng = np.random.default_rng(2)
        records = [
            ("ok1", ("# " + _aa_seq(rng, 10)).encode()),
            ("long1", b"X" * (BYTE_CFG.seq_len + 5)),
        ]
        summary = run_batch_score(model, params, records,
                                  str(tmp_path / "s"), batch_size=2,
                                  resume=False)
        assert summary["n_scored"] == 1 and summary["n_skipped"] == 1


class TestScoreStages:
    def test_a_batch_yields_the_four_stages_and_the_summary_it_had(
        self, byte_model, tmp_path
    ):
        """One batch = one score/collate, score/step, score/fetch,
        score/write and score/journal, in that order and not nested; the
        summary keeps its keys and its ``times`` their meaning (the
        journal line is no part of ``write``), fed from the same clock
        reads."""
        import time

        from progen_tpu.telemetry.spans import get_telemetry
        from progen_tpu.workloads import run_batch_score

        model, params = byte_model
        rng = np.random.default_rng(5)
        records = [(f"t{i}", ("# " + _aa_seq(rng, 12)).encode())
                   for i in range(8)]
        t0 = time.perf_counter()
        summary = run_batch_score(model, params, records,
                                  str(tmp_path / "s"), batch_size=4,
                                  resume=False)
        recs = [r for r in get_telemetry().stages(since=t0)
                if r[2].startswith("score/")]
        assert [r[2] for r in recs] == [
            "score/collate", "score/step", "score/fetch", "score/write",
            "score/journal",
        ] * 2
        assert all(r[1] is None for r in recs)
        assert set(summary) == {
            "n_scored", "n_skipped", "n_resumed", "tokens", "batches",
            "elapsed_s", "goodput_pct", "times", "stopped_early",
        }
        assert set(summary["times"]) == {"data", "step", "compile", "write"}
        assert summary["batches"] == 2 and summary["n_scored"] == 8
        total = {}
        for r in recs:
            total[r[2]] = total.get(r[2], 0.0) + r[4]
        times = summary["times"]  # rounded to the millisecond
        assert abs(times["data"] - total["score/collate"]) < 1e-3
        assert abs(times["write"] - total["score/write"]) < 1e-3
        assert abs(times["step"] + times["compile"]
                   - total["score/step"] - total["score/fetch"]) < 2e-3


class TestBatchScoreResume:
    def _records(self, n=12):
        rng = np.random.default_rng(3)
        return [
            (f"r{i}", ("# " + _aa_seq(rng, int(rng.integers(8, 24))))
             .encode("utf-8"))
            for i in range(n)
        ]

    def _all_ids(self, out_dir):
        ids = []
        for shard in sorted(Path(out_dir).glob("scores-*.jsonl")):
            for line in shard.read_text().splitlines():
                ids.append(json.loads(line)["id"])
        return ids

    def test_resume_completes_with_zero_duplicates(self, byte_model,
                                                   tmp_path):
        from progen_tpu.workloads import run_batch_score

        model, params = byte_model
        records = self._records()
        out_dir = str(tmp_path / "scores")
        partial = run_batch_score(model, params, list(records), out_dir,
                                  batch_size=4, max_batches=1,
                                  shard_size=4)
        assert partial["stopped_early"] and partial["n_scored"] == 4
        full = run_batch_score(model, params, list(records), out_dir,
                               batch_size=4, shard_size=4)
        assert full["n_resumed"] == 4 and full["n_scored"] == 8
        ids = self._all_ids(out_dir)
        assert sorted(ids) == sorted(r for r, _ in records)
        assert len(ids) == len(set(ids))  # exactly once each

    def test_torn_tail_truncated_and_rescored(self, byte_model, tmp_path):
        from progen_tpu.workloads import run_batch_score, scored_ids

        model, params = byte_model
        records = self._records(8)
        out_dir = str(tmp_path / "scores")
        run_batch_score(model, params, list(records), out_dir,
                        batch_size=4, shard_size=100)
        shard = sorted(Path(out_dir).glob("scores-*.jsonl"))[0]
        lines = shard.read_text().splitlines(keepends=True)
        torn_id = json.loads(lines[-1])["id"]
        # a SIGKILL mid-write leaves a partial last line: simulate it
        shard.write_text("".join(lines[:-1]) + lines[-1][: len(lines[-1]) // 2])
        seen, next_idx = scored_ids(out_dir)
        assert torn_id not in seen and len(seen) == 7
        assert next_idx == 1  # resume opens a FRESH shard
        summary = run_batch_score(model, params, list(records), out_dir,
                                  batch_size=4, shard_size=100)
        assert summary["n_scored"] == 1  # only the torn record again
        ids = self._all_ids(out_dir)
        assert sorted(ids) == sorted(r for r, _ in records)
        assert len(ids) == len(set(ids))

    def test_cli_sigkill_then_resume(self, tmp_path):
        """The acceptance kill case end to end: the REAL batch-score CLI,
        SIGKILLed by chaos injection after the 2nd durable batch, re-run
        without chaos — every FASTA id scored exactly once."""
        from progen_tpu.checkpoint import Package, get_checkpoint_fns

        model, params = _init(BYTE_CFG)
        ck = tmp_path / "ck"
        _, _, save = get_checkpoint_fns(str(ck))
        save(Package(0, {"params": params}, BYTE_CFG.to_dict(), "wl"))

        rng = np.random.default_rng(4)
        fasta = tmp_path / "cands.fasta"
        n_seqs = 12
        fasta.write_text("".join(
            f">c{i} synthetic\n{_aa_seq(rng, int(rng.integers(8, 24)))}\n"
            for i in range(n_seqs)
        ))
        out_dir = tmp_path / "scores"

        def run(chaos):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["PROGEN_CHAOS"] = chaos
            env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get(
                "PYTHONPATH", "")
            return subprocess.run(
                [sys.executable, "-m", "progen_tpu.cli.batch_score",
                 "--checkpoint_path", str(ck), "--input", str(fasta),
                 "--out_dir", str(out_dir), "--batch_size", "4",
                 "--no-logprobs"],
                env=env, capture_output=True, text=True, timeout=300,
                cwd=str(tmp_path),
            )

        killed = run("score/batch:kill@2")
        assert killed.returncode == -9, killed.stderr[-2000:]
        ids = []
        for shard in sorted(out_dir.glob("scores-*.jsonl")):
            with open(shard, "rb") as f:
                data = f.read()
            for line in data.split(b"\n"):
                if line.strip():
                    try:
                        ids.append(json.loads(line)["id"])
                    except ValueError:
                        pass  # the torn tail the resume will truncate
        assert 0 < len(ids) < n_seqs  # died mid-run, some work durable

        done = run("")
        assert done.returncode == 0, done.stderr[-2000:]
        summary = json.loads(done.stdout.strip().splitlines()[-1])
        assert summary["n_scored"] + summary["n_resumed"] == n_seqs
        ids = []
        for shard in sorted(out_dir.glob("scores-*.jsonl")):
            for line in shard.read_text().splitlines():
                ids.append(json.loads(line)["id"])
        assert sorted(ids) == sorted(f"c{i}" for i in range(n_seqs))
        assert len(ids) == len(set(ids))  # the PR's headline invariant
        # the journal is well-formed score-grammar all the way down
        for rec in (json.loads(ln) for ln in
                    (out_dir / "score_journal.jsonl").read_text()
                    .splitlines()):
            assert rec["ev"] == "score"
            assert rec["op"] in ("start", "resume", "batch", "skip", "done")


class TestMutagenesis:
    def test_scan_matches_loop_reference(self, byte_model):
        from progen_tpu.workloads import (
            mutagenesis_scan,
            reference_point_mutant_nll,
        )

        model, params = byte_model
        sequence = "MKTAYI"
        report = mutagenesis_scan(model, params, sequence, chunk=8, top=5)
        assert report["nll"].shape == (6, 20)
        # spot-check the vmapped batch against the un-vmapped oracle
        for pos, aa_idx in ((0, 3), (2, 0), (5, 17)):
            aa = report["alphabet"][aa_idx]
            ref = reference_point_mutant_nll(
                model, params, sequence, position=pos, aa=aa
            )
            assert np.isclose(report["nll"][pos, aa_idx], ref, atol=1e-4), (
                pos, aa, report["nll"][pos, aa_idx], ref,
            )

    def test_wild_type_nll_from_same_batch(self, byte_model):
        from progen_tpu.workloads import (
            mutagenesis_scan,
            reference_point_mutant_nll,
        )

        model, params = byte_model
        sequence = "MKTAYI"
        report = mutagenesis_scan(model, params, sequence, chunk=8)
        # wt via the reference scorer: "mutate" position 0 to itself
        ref = reference_point_mutant_nll(
            model, params, sequence, position=0, aa=sequence[0]
        )
        assert np.isclose(report["wt_nll"], ref, atol=1e-4)

    def test_top_excludes_self_substitutions(self, byte_model):
        from progen_tpu.workloads import mutagenesis_scan

        model, params = byte_model
        sequence = "MKTAYI"
        report = mutagenesis_scan(model, params, sequence, chunk=8, top=200)
        assert report["top"]  # 6 * 19 candidates
        assert len(report["top"]) == 6 * 19
        for e in report["top"]:
            assert e["aa"] != sequence[e["pos"]]
            assert e["wt"] == sequence[e["pos"]]
        deltas = [e["delta_nll"] for e in report["top"]]
        assert deltas == sorted(deltas, reverse=True)

    def test_positions_subset_and_errors(self, byte_model):
        from progen_tpu.workloads import mutagenesis_scan

        model, params = byte_model
        report = mutagenesis_scan(model, params, "MKTAYI",
                                  positions=[1, 4], chunk=8)
        assert report["positions"] == [1, 4]
        assert report["nll"].shape == (2, 20)
        with pytest.raises(ValueError):
            mutagenesis_scan(model, params, "MKTAYI", positions=[9])
        with pytest.raises(ValueError):
            mutagenesis_scan(model, params, "")
