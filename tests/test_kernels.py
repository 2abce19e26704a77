"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lut_lookup import (lut_lookup_pallas, two_level_exact,
                                      two_level_table)
from repro.kernels.masked_matmul import masked_matmul_pallas
from repro.kernels.ops import interpret_mode


def _indices(n_out, n_in, fan_in, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([np.sort(rng.choice(n_in, fan_in, replace=False))
                    for _ in range(n_out)])
    return jnp.asarray(idx.astype(np.int32))


# ---------------------------------------------------------------------------
# lut_lookup
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gather", ["compare", "two_level"])
@pytest.mark.parametrize("batch,n_in,n_out,fan_in,bw,block_b,block_o", [
    (4, 8, 8, 2, 1, 16, 32),
    (17, 12, 9, 3, 2, 16, 32),      # non-divisible batch/neurons
    (64, 32, 16, 2, 3, 16, 32),
    (256, 64, 64, 4, 2, 16, 32),    # multi-block batch
    (33, 16, 200, 3, 1, 16, 32),    # multi-block neurons
    (8, 24, 5, 6, 2, 16, 32),       # 12-bit tables, multiple e-chunks
    # chip tile sizes: 1024- and 4096-entry tables, 200 neurons (not a
    # multiple of block_o) and 130 rows (not a multiple of block_b)
    (130, 40, 200, 5, 2, 128, 128),
    (130, 40, 200, 6, 2, 128, 128),
])
def test_lut_lookup_matches_ref(batch, n_in, n_out, fan_in, bw, block_b,
                                block_o, gather):
    key = jax.random.PRNGKey(batch + n_out)
    codes = jax.random.randint(key, (batch, n_in), 0, 2 ** bw,
                               dtype=jnp.int32)
    idx = _indices(n_out, n_in, fan_in, seed=n_out)
    table = jax.random.randint(jax.random.PRNGKey(1), (n_out,
                               2 ** (fan_in * bw)), 0, 2 ** bw,
                               dtype=jnp.int32)
    assert two_level_exact(table, bw)
    kernel_table = table if gather == "compare" else two_level_table(table)
    got = lut_lookup_pallas(codes, idx, kernel_table, bw, block_b=block_b,
                            block_o=block_o, e_chunk=64,
                            interpret=interpret_mode())
    want = ref.lut_lookup_ref(codes, idx, table, bw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_lookup_matches_truth_table_layer():
    """The kernel result == core.table_infer layer forward (the network-level
    semantics the paper verifies functionally)."""
    from repro.core import layers as L
    from repro.core.quantize import QuantizerCfg, codes as qcodes
    from repro.core.table_infer import layer_table_forward
    from repro.core.truth_table import generate_sparse_linear_table

    cfg = L.SparseLinearCfg(in_features=16, out_features=12, fan_in=3,
                            bw_in=2)
    layer = L.sparse_linear_init(cfg, jax.random.PRNGKey(0))
    tt = generate_sparse_linear_table(cfg, layer, QuantizerCfg(2))
    x = jax.random.uniform(jax.random.PRNGKey(1), (40, 16), minval=-1,
                           maxval=3)
    c = qcodes(cfg.in_quant, x)
    want = layer_table_forward(tt, c)
    got = lut_lookup_pallas(c, jnp.asarray(tt.indices),
                            jnp.asarray(tt.table), tt.bw_in,
                            interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# masked_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8), (33, 70, 19), (128, 256, 64), (130, 100, 50),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_matmul_matches_ref(m, k, n, dtype):
    key = jax.random.PRNGKey(m * n)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = jax.random.normal(ks[1], (k, n), dtype)
    mask = (jax.random.uniform(ks[2], (k, n)) > 0.6).astype(dtype)
    b = jax.random.normal(ks[3], (n,), dtype)
    got = masked_matmul_pallas(x, w, mask, b, block_m=32, block_n=32,
                               block_k=32, interpret=interpret_mode())
    want = ref.masked_matmul_ref(x, w, mask, b)
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=1e-3)


def test_masked_matmul_respects_mask_exactly():
    """Zeroed weights contribute nothing even with huge magnitudes."""
    x = jnp.ones((4, 8))
    w = jnp.full((8, 4), 1e9)
    mask = jnp.zeros((8, 4)).at[0, :].set(1.0)
    got = masked_matmul_pallas(x, w, mask, interpret=interpret_mode())
    np.testing.assert_allclose(np.asarray(got), 1e9)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 2, 2, 64, 16),       # MHA
    (2, 4, 2, 96, 32),       # GQA, non-divisible seq vs block
    (1, 8, 1, 128, 16),      # MQA
    (2, 4, 4, 250, 8),       # ragged seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal):
    key = jax.random.PRNGKey(s + hq)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=32,
                                 block_k=32, interpret=interpret_mode())
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("window", [16, 64, 1024])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 16), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=32, block_k=32,
                                 interpret=interpret_mode())
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=32,
                                 block_k=32, interpret=interpret_mode())
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
