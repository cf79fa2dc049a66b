"""Sampling CLI — generate a protein sequence from the latest checkpoint.

Parity with /root/reference/sample.py:23-71: the model is rebuilt purely
from the checkpoint's stored config (sample.py:46-47), the prime is
byte-tokenized, decode runs with top_k=25 and add_bos=True, and the output
after the prime is printed. Prime conventions (README.md:82-86):
``"[tax=Mammalia] #"`` generates a sequence; ``"SEQ #"`` generates
annotations.

Fixed-position infilling (progen_tpu/workloads/infill.py): ``--template
"MK?LV??G"`` keeps the non-``?`` characters verbatim and samples the
free slots; the leading frozen run primes the decode, so --prime and
--template are mutually exclusive.

Run: python -m progen_tpu.cli.sample --prime "[tax=Mammalia] #"
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file()  # XLA/env flags before jax import (ref train.py:1-2)

import sys

import click
import numpy as np

import jax


@click.command()
@click.option("--seed", default=42)
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--prime", default="")
@click.option("--top_k", default=25)
@click.option("--temperature", default=1.0,
              help="logit temperature before top-k/top-p filtering "
                   "(1.0 = reference parity)")
@click.option("--top_p", default=None, type=float,
              help="nucleus sampling: keep the smallest top-probability "
                   "set with cumulative mass >= p (combines with --top_k; "
                   "unset = reference parity)")
@click.option(
    "--naive",
    default=False,
    is_flag=True,
    help="reference-style full forward per token instead of the KV cache",
)
@click.option(
    "--num_samples",
    default=1,
    help="decode this many sequences from the prime in one batched "
    "KV-cache pass (--naive switches to the full-forward batched decode)",
)
@click.option("--template", default=None, type=str,
              help="infilling template: non-free characters are frozen "
                   "verbatim, --free_char slots are sampled (replaces "
                   "--prime; the frozen prefix primes the decode)")
@click.option("--free_char", default="?",
              help="the free-position sentinel inside --template")
def main(seed, checkpoint_path, prime, top_k, temperature, top_p,
         naive, num_samples, template, free_char):
    from progen_tpu.checkpoint import get_checkpoint_fns
    from progen_tpu.data.tokenizer import decode_tokens, encode_tokens
    from progen_tpu.models import build_model, require_progen
    from progen_tpu.sampling import (
        sample,
        sample_batched,
        sample_fast,
        sample_fast_batched,
    )

    _, get_last, _ = get_checkpoint_fns(checkpoint_path)
    # params-only restore: sampling never needs the optimizer moments
    pkg = get_last.restore_params()
    if pkg is None:
        sys.exit(f"no checkpoints found at {checkpoint_path}")

    model = require_progen(build_model(pkg.model_config), "cli.sample")
    config = model.config
    params = pkg.state

    num_params = sum(int(np.size(x)) for x in jax.tree.leaves(params))
    print(f"params: {num_params:,}")
    print(f"sequence length: {config.seq_len}")
    print(f"trained for {max(pkg.next_seq_index, 0):,} sequences")

    length = config.seq_len
    tpl_arr = frz_arr = None
    if template is not None:
        from progen_tpu.workloads.infill import (
            infill_request_arrays,
            parse_template,
        )

        if prime:
            sys.exit("--template and --prime are mutually exclusive "
                     "(the template's frozen prefix is the prime)")
        if num_samples > 1:
            sys.exit("--template decodes one sequence (--num_samples 1)")
        toks, frz = parse_template(template, free_char)
        prime_tokens, length, tpl_arr, frz_arr = infill_request_arrays(
            toks, frz, add_bos=True
        )
        prime = decode_tokens(prime_tokens)
    else:
        prime_tokens = np.asarray(encode_tokens(prime), dtype=np.int32)
    prime_length = len(prime_tokens) + 1  # +1 for BOS (sample.py:67)

    if num_samples > 1:
        primes = np.tile(prime_tokens, (num_samples, 1))
        batched_fn = sample_batched if naive else sample_fast_batched
        sampled = batched_fn(
            jax.random.PRNGKey(seed), model, params, primes,
            config.seq_len, top_k=top_k, add_bos=True,
            temperature=temperature, top_p=top_p,
        )
        print("\n", prime, "\n", "*" * 40)
        for row in np.asarray(sampled):
            print(decode_tokens(row[prime_length:]), "\n", "-" * 40)
        return

    sample_fn = sample if naive else sample_fast
    sampled = sample_fn(
        jax.random.PRNGKey(seed),
        model,
        params,
        prime_tokens,
        length,
        top_k=top_k,
        add_bos=True,
        temperature=temperature,
        top_p=top_p,
        template=tpl_arr,
        frozen=frz_arr,
    )
    sampled_str = decode_tokens(np.asarray(sampled)[prime_length:])
    print("\n", prime, "\n", "*" * 40, "\n", sampled_str)


if __name__ == "__main__":
    main()
