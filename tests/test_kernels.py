"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.kernels.wcoj_intersect.ops import gather_rows, wcoj_intersect
from repro.kernels.wcoj_intersect.ref import wcoj_intersect_ref
from repro.kernels.wcoj_intersect.wcoj_intersect import (TILE_ELEMS,
                                                         block_rows_for)


# ------------------------------------------------------------ wcoj_intersect

@pytest.mark.parametrize("R,D", [(64, 16), (300, 64), (17, 128), (512, 8)])
def test_wcoj_shapes(R, D):
    rng = np.random.default_rng(R * D)
    adj = np.sort(rng.integers(0, 5 * D, size=(R, D)), axis=1)
    deg = rng.integers(0, D + 1, size=R)
    adj = np.where(np.arange(D)[None] < deg[:, None], adj, -1)
    adj = np.where(adj < 0, np.iinfo(np.int32).max, adj)
    adj = np.sort(adj, axis=1)
    adj[adj == np.iinfo(np.int32).max] = -1
    tgt = rng.integers(0, 5 * D, size=R).astype(np.int32)
    hit = deg > 0
    tgt[hit] = adj[np.arange(R), np.maximum(deg - 1, 0)][hit]
    adj_t = jnp.asarray(adj.T.astype(np.int32))      # lane-dense [D, R]
    f1, p1 = wcoj_intersect(adj_t, jnp.asarray(tgt), interpret=True)
    f2, p2 = wcoj_intersect_ref(adj_t, jnp.asarray(tgt))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


@pytest.mark.parametrize("R,D", [(4100, 1024), (40000, 8), (300, 1)])
def test_wcoj_default_tiles_match_ref(R, D):
    """The kernel's own row tile (``block_rows_for``): legal for the TPU
    (the whole row range, or lane-aligned within the VMEM budget) and
    exact across a multi-step grid with a padded tail."""
    blk = block_rows_for(R, D)
    assert blk == R or (blk % 128 == 0 and blk * max(D, 8) <= TILE_ELEMS)
    rng = np.random.default_rng(R + D)
    adj = np.sort(rng.integers(0, 4 * D, size=(D, R)), axis=0)
    adj = np.where(np.diff(adj, axis=0, prepend=-1) == 0, -1, adj)
    tgt = rng.integers(0, 4 * D, size=R)
    adj_t = jnp.asarray(adj.astype(np.int32))
    f1, p1 = wcoj_intersect(adj_t, jnp.asarray(tgt, jnp.int32),
                            interpret=True)
    f2, p2 = wcoj_intersect_ref(adj_t, jnp.asarray(tgt, jnp.int32))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    assert np.asarray(f1).any()


def test_wcoj_from_csr(tiny_store):
    from repro.core.schema import EdgeTriple
    t = EdgeTriple("PERSON", "KNOWS", "PERSON")
    csr = tiny_store.out_csr[t]
    rng = np.random.default_rng(0)
    rows = rng.integers(0, tiny_store.v_count["PERSON"], size=40)
    adj = gather_rows(jnp.asarray(csr.indices), jnp.asarray(csr.indptr),
                      jnp.asarray(rows), d_max=64)
    targets = jnp.asarray(rng.integers(0, tiny_store.n_vertices, 40))
    f, p = wcoj_intersect(adj.astype(jnp.int32),
                          targets.astype(jnp.int32), interpret=True)
    for i in range(40):
        seg = csr.indices[csr.indptr[rows[i]]:csr.indptr[rows[i] + 1]]
        assert bool(f[i]) == (int(targets[i]) in seg.tolist())


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("B,H,Hkv,S,d,causal,window,cap,dtype", [
    (2, 4, 2, 128, 32, True, None, None, jnp.float32),
    (1, 2, 2, 96, 16, True, 24, 50.0, jnp.float32),
    (2, 2, 1, 64, 64, True, None, 30.0, jnp.float32),
    (1, 4, 4, 80, 24, True, None, None, jnp.float32),
    (1, 2, 2, 64, 32, True, None, None, jnp.bfloat16),
])
def test_flash_attention_sweep(B, H, Hkv, S, d, causal, window, cap, dtype):
    rng = np.random.default_rng(S + d)
    q = jnp.asarray(rng.normal(size=(B, H, S, d)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, d)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=32, block_kv=32, interpret=True)
    kk = jnp.repeat(k, H // Hkv, axis=1)
    vv = jnp.repeat(v, H // Hkv, axis=1)
    ref = attention_ref(q, kk, vv, causal=causal, window=window, softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_attention():
    """Kernel agrees with the model's jnp online-softmax attention path."""
    from repro.models.transformer import TransformerConfig, _block_attention
    cfg = TransformerConfig(name="t", n_layers=1, d_model=64, n_heads=4,
                            n_kv_heads=2, d_ff=64, vocab_size=16,
                            block_q=16, block_kv=16, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    B, S, K, G, hd = 2, 64, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, K, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    model_out = _block_attention(q, k, v, cfg, q_start=0, kv_len=S,
                                 is_local=jnp.asarray(False))
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B, K * G, S, hd)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    kernel_out = flash_attention(qf, kf, vf, causal=True, block_q=16,
                                 block_kv=16, interpret=True)
    kernel_out = kernel_out.reshape(B, K, G, S, hd).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(model_out), np.asarray(kernel_out),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------ grouped matmul

@pytest.mark.parametrize("G,M,K,N,dtype", [
    (4, 64, 96, 80, jnp.float32),
    (2, 128, 128, 128, jnp.float32),
    (3, 37, 65, 50, jnp.float32),
    (2, 64, 64, 64, jnp.bfloat16),
    (1, 256, 32, 16, jnp.float32),
])
def test_grouped_matmul_sweep(G, M, K, N, dtype):
    rng = np.random.default_rng(G * M)
    x = jnp.asarray(rng.normal(size=(G, M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(G, K, N)), dtype)
    o = grouped_matmul(x, w, block_m=32, block_n=32, block_k=32,
                       interpret=True)
    r = grouped_matmul_ref(x, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


# -------------------------------------------------------------- embedding bag

@pytest.mark.parametrize("B,L,V,D", [(100, 6, 1000, 32), (32, 1, 64, 8),
                                     (7, 12, 333, 16)])
def test_embedding_bag_sweep(B, L, V, D):
    rng = np.random.default_rng(B + V)
    ids = rng.integers(-1, V, size=(B, L)).astype(np.int32)
    tab = rng.normal(size=(V, D)).astype(np.float32)
    o = embedding_bag(jnp.asarray(ids), jnp.asarray(tab), block_b=32,
                      block_v=128, interpret=True)
    r = embedding_bag_ref(jnp.asarray(ids), jnp.asarray(tab))
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-4,
                               atol=1e-4)


def test_embedding_bag_matches_model_path():
    """Kernel agrees with the recsys model's take+mask formulation."""
    from repro.models import recsys
    cfg = recsys.WideDeepConfig(vocab_sizes=tuple([64] * 4), n_sparse=4,
                                wide_vocab=32, n_items=16, item_dim=8,
                                mlp=(16,), max_bag=3)
    rng = np.random.default_rng(0)
    ids = rng.integers(-1, 64, size=(10, 4, 3)).astype(np.int32)
    table = rng.normal(size=(cfg.total_rows, cfg.embed_dim)).astype(np.float32)
    offsets = jnp.asarray(cfg.field_offsets())
    model_out = recsys.embedding_bag(jnp.asarray(table), jnp.asarray(ids),
                                     offsets)
    flat_ids = np.where(ids >= 0,
                        ids + np.asarray(cfg.field_offsets())[None, :, None],
                        -1)
    per_field = []
    for f in range(4):
        per_field.append(np.asarray(embedding_bag(
            jnp.asarray(flat_ids[:, f]), jnp.asarray(table), interpret=True)))
    kernel_out = np.concatenate(per_field, axis=-1)
    np.testing.assert_allclose(np.asarray(model_out), kernel_out, rtol=1e-4,
                               atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 50), st.integers(1, 8), st.integers(2, 200))
def test_embedding_bag_property(B, L, V):
    rng = np.random.default_rng(B * L * V)
    ids = rng.integers(-1, V, size=(B, L)).astype(np.int32)
    tab = rng.normal(size=(V, 8)).astype(np.float32)
    o = embedding_bag(jnp.asarray(ids), jnp.asarray(tab), block_b=16,
                      block_v=64, interpret=True)
    r = embedding_bag_ref(jnp.asarray(ids), jnp.asarray(tab))
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-4,
                               atol=1e-4)
