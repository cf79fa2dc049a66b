"""Mutagenesis scan CLI — score every point mutant of a sequence.

In-silico deep mutational scanning (progen_tpu/workloads/mutagenesis.py):
the L x 20 substitution matrix is built and scored inside one compiled
program, ranked by ``delta_nll = wt_nll - mutant_nll`` (positive = the
model prefers the mutant). The full (positions x alphabet) NLL matrix
plus the top-K table can be written as JSON with ``--out``.

Run: python -m progen_tpu.cli.scan --checkpoint_path ./ckpts \
         --sequence MKTAYIAKQR --context "[tax=Mammalia]"
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file()  # XLA/env flags before jax import (ref train.py:1-2)

import json
import sys

import click


def _parse_positions(spec, seq_len):
    """"START:END" (0-based, half-open) or a comma list -> indices."""
    if spec is None:
        return None
    if ":" in spec:
        start_s, end_s = spec.split(":", 1)
        start = int(start_s) if start_s else 0
        end = int(end_s) if end_s else seq_len
        return range(start, end)
    return [int(p) for p in spec.split(",") if p.strip()]


@click.command()
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--sequence", default=None,
              help="the amino-acid sequence to scan (or use --fasta)")
@click.option("--fasta", default=None, type=str,
              help="take the sequence from this FASTA file instead")
@click.option("--index", default=0,
              help="which FASTA record to scan (0-based)")
@click.option("--context", default="",
              help="conditioning tag (scored as 'context # SEQ')")
@click.option("--positions", default=None, type=str,
              help="residues to scan: 'START:END' (0-based, half-open) "
                   "or 'p1,p2,...' (default: every position)")
@click.option("--top", default=20, help="report the K best substitutions")
@click.option("--chunk", default=32,
              help="mutants scored per lax.map step (peak-memory knob)")
@click.option("--out", "out_path", default=None, type=str,
              help="write the full report (NLL matrix + top table) as "
                   "JSON here")
def main(checkpoint_path, sequence, fasta, index, context, positions,
         top, chunk, out_path):
    from progen_tpu.checkpoint import get_checkpoint_fns
    from progen_tpu.models import build_model, require_progen
    from progen_tpu.workloads import mutagenesis_scan

    if (sequence is None) == (fasta is None):
        sys.exit("pass exactly one of --sequence / --fasta")
    if fasta is not None:
        from progen_tpu.data.fasta import parse_fasta

        recs = list(parse_fasta(fasta))
        if not 0 <= index < len(recs):
            sys.exit(f"--index {index} outside {len(recs)} FASTA records")
        sequence = recs[index][1]

    _, get_last, _ = get_checkpoint_fns(checkpoint_path)
    pkg = get_last.restore_params()  # params only: no optimizer moments
    if pkg is None:
        sys.exit(f"no checkpoints found at {checkpoint_path}")
    model = require_progen(build_model(pkg.model_config), "cli.scan")

    report = mutagenesis_scan(
        model, pkg.state, sequence, context=context,
        positions=_parse_positions(positions, len(sequence)),
        chunk=chunk, top=top,
    )
    print(f"wild-type NLL: {report['wt_nll']:.4f} "
          f"({len(report['positions'])} positions x "
          f"{len(report['alphabet'])} substitutions)")
    print(f"{'pos':>5} {'wt':>3} {'mut':>4} {'nll':>9} {'delta_nll':>10}")
    for e in report["top"]:
        print(f"{e['pos']:>5} {e['wt']:>3} {e['aa']:>4} "
              f"{e['nll']:>9.4f} {e['delta_nll']:>+10.4f}")

    if out_path:
        doc = dict(report)
        doc["nll"] = [[float(x) for x in row] for row in report["nll"]]
        doc["positions"] = [int(p) for p in report["positions"]]
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2)
        print(f"report written to {out_path}")


if __name__ == "__main__":
    main()
