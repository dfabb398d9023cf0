"""The serving engines' one sampler, run on the device.

Every program that produces logits for the engines samples them in
place and returns tokens: the host reads one int32 per row instead of
a row of logits.  Each row carries its own temperature and PRNG key:

* ``temperature <= 0``: the row's argmax;
* ``temperature > 0``: a draw from ``softmax(row / temperature)`` with
  the key ``jax.random.PRNGKey(rid * KEY_STRIDE + n_gen)`` — each step
  of each request gets a distinct key, named by the request's id and
  the tokens it generated before this one.

Rows that sample nothing (masked prefill passengers, free slots) carry
temperature 0; their token is discarded.  A batch with no row above 0
never pays for the draw: the branch is chosen on the device.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KEY_STRIDE = 7919
_WORD = 0xFFFFFFFF


def key_words(seed: int) -> Tuple[int, int]:
    """`jax.random.PRNGKey(seed)`'s two uint32 words, on the host: the
    high word then the low.  Without 64-bit types JAX takes the seed
    modulo 2**32, so the high word is 0."""
    hi = (seed >> 32) & _WORD if jax.config.jax_enable_x64 else 0
    return hi, seed & _WORD


def sampler_inputs(rows: Iterable[tuple], n: int) -> Dict[str, np.ndarray]:
    """Host arrays for `sample_tokens` over a batch of `n` rows.

    `rows` holds (row index, request, tokens generated before this
    one) for every row that samples; the others stay at temperature 0
    with a zero key."""
    temperature = np.zeros(n, np.float32)
    key_data = np.zeros((n, 2), np.uint32)
    for i, req, n_gen in rows:
        if req.temperature > 0:
            temperature[i] = req.temperature
            key_data[i] = key_words(req.rid * KEY_STRIDE + n_gen)
    return {"temperature": temperature, "key_data": key_data}


def sample_tokens(logits: jnp.ndarray, temperature: jnp.ndarray,
                  key_data: jnp.ndarray) -> jnp.ndarray:
    """(B, V) logits, (B,) float32 temperatures and (B, 2) uint32 key
    words -> (B,) int32 tokens."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    hot = temperature > 0

    def draw(_):
        t = jnp.where(hot, temperature, 1.0)[:, None]
        drawn = jax.vmap(jax.random.categorical)(key_data, logits / t)
        return jnp.where(hot, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(hot), draw, lambda _: greedy, None)


def sample_step(step, params, state, batch: dict, cfg):
    """Run a model step whose batch also carries the sampler's inputs
    (`sampler_inputs`' keys) and sample its logits: (tokens, state),
    or ((tokens, load), state) for a step that also returns an expert
    load (`decode_step_paged_load`)."""
    batch = dict(batch)
    temperature = batch.pop("temperature")
    key_data = batch.pop("key_data")
    logits, state, *load = step(params, state, batch, cfg)
    tokens = sample_tokens(logits, temperature, key_data)
    return ((tokens, *load) if load else tokens), state


#: The sampler alone, for a caller that already holds a prefill's logits
sample_rows = jax.jit(sample_tokens)
