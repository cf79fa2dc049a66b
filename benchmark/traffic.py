"""The one traffic generator: turns a traffic file's parameters and
``--seed`` into weights, requests, records or batches. The seed chooses
bytes and ORDER only; the multiset of sizes is the file's, the same in
every run, so runs of different seeds do the same work."""

from __future__ import annotations

import numpy as np


def fold_key(seed: int):
    """A PRNG key from any whole-number seed (they exceed 32 bits)."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def seeded_params(model, seq_len: int, seed: int, device):
    """The model's parameters, made on the device in one jitted call from
    the seed, in the type a checkpoint holds them."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    with jax.default_device(device):
        return jax.jit(
            lambda k: meta.unbox(
                model.init(k, jnp.zeros((1, seq_len), jnp.int32))
            )["params"]
        )(fold_key(seed))


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), sum(stream.encode())])


def paired_cycles(a_values, b_values, rng):
    """Endless (a, b) pairs. One cycle uses every pair of the grid exactly
    once; each block of len(a) consecutive pairs uses every a once and, when
    len(a) <= len(b), no b twice — so any stretch of the stream has nearly
    the grid's own mix, whatever the order. The seed permutes labels, blocks
    and the order inside a block."""
    na, nb = len(a_values), len(b_values)
    while True:
        a = rng.permutation(a_values)
        b = rng.permutation(b_values)
        for k in rng.permutation(nb):
            block = [(int(a[i]), int(b[(i + k) % nb])) for i in range(na)]
            for j in rng.permutation(na):
                yield block[j]


def gen_requests(traffic: dict, seed: int):
    """Endless (prompt tokens, output length) for the generation cells.
    Prompt bytes are nonzero (0 is pad/BOS/EOS)."""
    rng = rng_for(seed, "gen")
    pairs = paired_cycles(
        traffic["prompt_lengths"], traffic["output_lengths"], rng
    )
    for p_len, out_len in pairs:
        yield rng.integers(1, 256, size=p_len, dtype=np.int32), out_len


def score_records(traffic: dict, seed: int):
    """Endless (id, raw bytes) FASTA-like records: '# ' + residues, total
    length drawn cycle by cycle from the file's multiset."""
    rng = rng_for(seed, "score")
    alphabet = np.frombuffer(traffic["alphabet"].encode(), dtype=np.uint8)
    i = 0
    while True:
        for n in rng.permutation(traffic["lengths"]):
            body = alphabet[rng.integers(0, len(alphabet), size=int(n) - 2)]
            yield f"r{i}", b"# " + body.tobytes()
            i += 1


def train_batches(traffic: dict, seq_len: int, seed: int) -> list:
    """``distinct_batches`` host batches (grad_accum, micro_batch * data,
    seq_len + 1) of nonzero bytes: full rows, so every position trains."""
    rng = rng_for(seed, "train")
    shape = (
        traffic["grad_accum"],
        traffic["micro_batch"] * traffic["mesh"]["data"],
        seq_len + 1,
    )
    return [
        rng.integers(1, 256, size=shape, dtype=np.int32)
        for _ in range(traffic["distinct_batches"])
    ]
