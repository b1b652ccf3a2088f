"""The KV row write (``layers._write_kv_rows``) against the
``leaf.at[idx, :, offs, :]`` scatter it replaced: bit for bit, for each
way the decode and prefill paths call it."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import _write_kv_rows

KH, HD = 2, 8


def _dense_ragged(rng, window=False, past_end=False):
    """Dense (B, Kh, Smax, hd) cache, one row per slot at its position;
    a windowed layer writes at ``pos mod Smax``, past the ring's end, and
    a global layer drops a row whose position lies past ``Smax``."""
    B, smax = 4, 16
    pos = np.array([0, 7, 15, 13], np.int32)
    if window or past_end:
        pos = pos + np.array([0, smax, 2 * smax, 5 * smax], np.int32)
    slots = np.mod(pos, smax) if window else pos
    leaf = rng.standard_normal((B, KH, smax, HD))
    return leaf, np.arange(B, dtype=np.int32), slots, (B, KH, HD)


def _paged_decode(rng):
    """Block pool and tables; slots 1 and 3 are idle (all-null tables),
    so two distinct rows land on the same row of the null block 0."""
    B, M, bs = 4, 3, 4
    n_blocks = 1 + B * M
    tables = np.zeros((B, M), np.int32)
    tables[0, :2] = [5, 2]
    tables[2, :3] = [9, 11, 1]
    p0 = np.array([6, 2, 9, 2], np.int32)
    pid = tables[np.arange(B), p0 // bs]
    leaf = rng.standard_normal((n_blocks, KH, bs, HD))
    return leaf, pid, p0 % bs, (B, KH, HD)


def _paged_prefill(rng):
    """One slot's prompt through its table; the bucket's padded tail runs
    past the slot's blocks into null entries (block 0)."""
    M, bs, S = 5, 4, 20
    n_blocks = 1 + 2 * M
    table = np.array([7, 3, 10, 0, 0], np.int32)
    pos = np.arange(S, dtype=np.int32)
    leaf = rng.standard_normal((n_blocks, KH, bs, HD))
    return leaf, table[pos // bs], pos % bs, (S, KH, HD)


CASES = {
    "dense_ragged": _dense_ragged,
    "dense_windowed_wrap": lambda rng: _dense_ragged(rng, window=True),
    "dense_past_end": lambda rng: _dense_ragged(rng, past_end=True),
    "paged_decode_idle_null": _paged_decode,
    "paged_prefill": _paged_prefill,
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_write_kv_rows_matches_scatter(case, dtype):
    rng = np.random.default_rng(3)
    leaf, idx, offs, new_shape = CASES[case](rng)
    leaf = jnp.asarray(leaf, dtype)
    new = jnp.asarray(rng.standard_normal(new_shape), dtype)
    idx, offs = jnp.asarray(idx), jnp.asarray(offs)
    ref = leaf.at[idx, :, offs, :].set(new)
    got = _write_kv_rows(leaf, idx, offs, new)
    assert got.shape == leaf.shape and got.dtype == leaf.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(ref.astype(jnp.float32)))
    assert not np.array_equal(np.asarray(got.astype(jnp.float32)),
                              np.asarray(leaf.astype(jnp.float32)))
