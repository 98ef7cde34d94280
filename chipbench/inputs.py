"""Keys and tokens from ``--seed``, made by the benchmark: the program
receives them, and the reference makes the same ones again.  Each model
type makes its weights from ``key_of(seed, WEIGHTS)``
(``chipbench/models/``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_of(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one stream of a seed of any size up to 64 bits."""
    k = jax.random.PRNGKey(seed % (1 << 32))
    k = jax.random.fold_in(k, (seed >> 32) % (1 << 32))
    return jax.random.fold_in(k, stream)


WEIGHTS, TOKENS = 1, 2


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _rows(key, step, start, n, seq, vocab):
    key = jax.random.fold_in(key, step)
    return jax.vmap(lambda r: jax.random.randint(
        jax.random.fold_in(key, r), (seq,), 0, vocab, jnp.int32))(
            start + jnp.arange(n, dtype=jnp.int32))


class TokenFeed:
    """Token rows of each step: row r of step s is drawn from
    (seed, s, r) alone, so any slice of a step's batch is the same rows
    whoever asks.  Uniform over the held vocabulary."""

    def __init__(self, seed: int, vocab: int, seq_len: int,
                 global_batch: int):
        self.key = key_of(seed, TOKENS)
        self.vocab, self.seq_len = vocab, seq_len
        self.global_batch = global_batch

    def tokens(self, step: int, start: int = 0, n=None) -> jax.Array:
        n = self.global_batch if n is None else n
        return _rows(self.key, step, start, n, self.seq_len, self.vocab)

    def batch(self, step: int, start: int = 0, n=None):
        return {"tokens": self.tokens(step, start, n)}
