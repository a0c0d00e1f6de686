"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag import gather_layout, gather_rows
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.segment_mm import (
    block_sparse_plan, block_spmm_xla, segment_mm, sorted_edge_slots,
    tiles_from_plan, to_block_sparse,
)
from repro.kernels.segment_mm.kernel import block_spmm_kernel
from repro.kernels.segment_mm.ref import spmm_ref


class TestSegmentMM:
    @pytest.mark.parametrize("n_src,n_dst,n_edges,f", [
        (300, 260, 2000, 70),
        (128, 128, 500, 128),
        (1000, 50, 4000, 32),   # many-to-few (high in-degree)
        (64, 700, 300, 16),     # sparse rows (many empty dst blocks)
    ])
    def test_matches_ref_shapes(self, n_src, n_dst, n_edges, f):
        rng = np.random.default_rng(n_src + n_dst)
        src = rng.integers(0, n_src, n_edges)
        dst = rng.integers(0, n_dst, n_edges)
        x = jnp.asarray(rng.standard_normal((n_src, f)).astype(np.float32))
        got = segment_mm(src, dst, x, n_dst, tn=64, tm=64, tf=64)
        want = spmm_ref(jnp.asarray(src), jnp.asarray(dst), x, n_dst)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-3, rtol=1e-3
        )

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(0)
        src = rng.integers(0, 100, 400)
        dst = rng.integers(0, 100, 400)
        x = jnp.asarray(rng.standard_normal((100, 64)), dtype=dtype)
        got = segment_mm(src, dst, x, 100, tn=32, tm=32, tf=32)
        want = spmm_ref(jnp.asarray(src), jnp.asarray(dst), x, 100)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol * 10, rtol=tol,
        )

    def test_edge_weights(self):
        rng = np.random.default_rng(1)
        src = rng.integers(0, 80, 300)
        dst = rng.integers(0, 80, 300)
        w = rng.standard_normal(300).astype(np.float32)
        x = jnp.asarray(rng.standard_normal((80, 32)).astype(np.float32))
        got = segment_mm(src, dst, x, 80, edge_weight=w, tn=16, tm=16, tf=32)
        want = spmm_ref(jnp.asarray(src), jnp.asarray(dst), x, 80, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=1e-3)

    @pytest.mark.parametrize("n_src,src_hi,n_dst,n_edges,f,t", [
        (300, 300, 260, 2000, 64, 32),
        (64, 64, 700, 300, 32, 32),      # many empty dst blocks
        (1000, 1000, 50, 4000, 32, 64),  # many-to-few
        (512, 100, 96, 200, 32, 32),     # edges reach only low src blocks
    ])
    def test_vjp_matches_xla_grad(self, n_src, src_hi, n_dst, n_edges, f,
                                  t):
        """The kernel's custom VJP (same kernel, source-sorted, transposed
        tiles) gives the XLA block path's gradient; source blocks no edge
        reaches get exactly zero."""
        rng = np.random.default_rng(n_src + n_edges)
        src = rng.integers(0, src_hi, n_edges)
        dst = rng.integers(0, n_dst, n_edges)
        rows, cols, blocks, ndb, n_src_pad = to_block_sparse(
            src, dst, n_dst, n_src, t, t
        )
        r, c, b = map(jnp.asarray, (rows, cols, blocks))
        x = jnp.asarray(rng.standard_normal((n_src_pad, f)), jnp.float32)
        g = jnp.asarray(rng.standard_normal((ndb * t, f)), jnp.float32)

        def grad(spmm):
            return jax.grad(lambda x: jnp.sum(spmm(x) * g))(x)

        got = grad(lambda x: block_spmm_kernel(r, c, b, x, ndb, tn=t, tm=t,
                                               tf=32))
        want = grad(lambda x: block_spmm_xla(r, c, b, x, ndb, tn=t, tm=t))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-6)
        unreached = np.setdiff1d(np.arange(n_src_pad // t), cols)
        for blk in unreached:
            assert not np.asarray(got)[blk * t:(blk + 1) * t].any()

    def test_block_sparse_format_complete(self):
        """Every dst block covered; blocks reproduce the adjacency."""
        rng = np.random.default_rng(2)
        src = rng.integers(0, 50, 100)
        dst = rng.integers(0, 90, 100)
        rows, cols, blocks, nb, _ = to_block_sparse(src, dst, 90, 50, 32, 32)
        assert set(range(nb)) <= set(rows.tolist())
        assert (np.diff(rows) >= 0).all()  # row-sorted
        total = blocks.sum()
        assert total == 100  # one unit per edge

    @pytest.mark.parametrize("n_src,n_dst,dst_hi,n_edges,t,masked,pad", [
        (300, 260, 260, 2000, 32, 0.0, 0),     # dense-ish, no padding edge
        (64, 700, 700, 300, 32, 0.3, 50),      # many empty dst blocks
        (1000, 50, 50, 4000, 8, 0.5, 1000),    # many-to-few, small tiles
        (512, 1024, 200, 600, 128, 0.2, 100),  # upper dst blocks all empty
        (256, 256, 256, 100, 128, 1.0, 10),    # every edge masked
        (128, 128, 128, 0, 128, 0.0, 5),       # no edges at all
    ])
    def test_device_tiles_equal_host_tiles(self, n_src, n_dst, dst_hi,
                                           n_edges, t, masked, pad):
        """Tiles scattered on the device from the sorted edge slots equal
        ``to_block_sparse``'s 0/1-weighted tiles bit for bit, with zero
        tiles up to the power-of-two tile count; duplicate edges add up,
        masked edges keep their tile but add nothing, and padding edges
        (slot = tile count) add nothing."""
        rng = np.random.default_rng(n_src + n_edges + t)
        src = rng.integers(0, n_src, n_edges)
        dst = rng.integers(0, dst_hi, n_edges)
        dup = rng.integers(0, max(n_edges, 1), n_edges // 4)
        src = np.concatenate([src, src[dup]])
        dst = np.concatenate([dst, dst[dup]])
        keep = rng.random(len(src)) >= masked
        rows, cols, blocks, ndb, _ = to_block_sparse(
            src, dst, n_dst, n_src, t, t, keep.astype(np.float32)
        )
        p_rows, p_cols, slot, off, p_ndb, _ = block_sparse_plan(
            src, dst, n_dst, n_src, t, t
        )
        assert np.array_equal(p_rows, rows) and np.array_equal(p_cols, cols)
        assert p_ndb == ndb
        nbp = 1 << (len(rows) - 1).bit_length()
        slot, off = sorted_edge_slots(slot, off, keep, int(keep.sum()) + pad,
                                      nbp, t * t)
        assert slot.dtype == off.dtype == np.int32
        assert (slot[keep.sum():] == nbp).all()
        # every index the scatter gets, padding included, is in bounds and
        # in sorted order: a dropped out-of-range index lost updates on TPU
        flat = np.minimum(slot, nbp - 1).astype(np.int64) * t * t + off
        assert (np.diff(flat) >= 0).all() and flat[-1] < nbp * t * t
        build = jax.jit(tiles_from_plan, static_argnums=(2, 3, 4))
        got = np.asarray(build(jnp.asarray(slot), jnp.asarray(off), nbp,
                               t, t))
        want = np.zeros((nbp, t, t), np.float32)
        want[: len(rows)] = blocks
        assert np.array_equal(got, want)
        assert got.sum() == keep.sum()


class TestFlashAttention:
    @pytest.mark.parametrize("s,d,causal", [
        (128, 64, True), (256, 64, True), (128, 128, False), (512, 32, True),
    ])
    def test_matches_ref(self, s, d, causal):
        key = jax.random.PRNGKey(s + d)
        q = jax.random.normal(key, (3, s, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (3, s, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (3, s, d))
        got = flash_attention_kernel(q, k, v, causal=causal,
                                     block_q=64, block_k=64)
        want = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("block_q,block_k", [(32, 32), (64, 128), (128, 64)])
    def test_block_shape_sweep(self, block_q, block_k):
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (2, 256, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 256, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 256, 32))
        got = flash_attention_kernel(q, k, v, causal=True,
                                     block_q=block_q, block_k=block_k)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_bf16(self):
        key = jax.random.PRNGKey(9)
        q = jax.random.normal(key, (2, 128, 64), jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 64), jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 64), jnp.bfloat16)
        got = flash_attention_kernel(q, k, v, causal=True, block_q=64, block_k=64)
        want = attention_ref(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), atol=4e-2, rtol=2e-2
        )

    def test_gqa_wrapper_matches_model_attention(self):
        from repro.models.lm.attention import dense_attention

        key = jax.random.PRNGKey(11)
        q = jax.random.normal(key, (2, 128, 8, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 2, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 2, 32))
        got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)


def _bag(table, idx, seg, n_bags, weights=None):
    """Sum-mode EmbeddingBag from the kernel: the Pallas row gather, then
    a weighted segment sum."""
    rows = gather_rows(gather_layout(table), idx, table.shape[1])
    if weights is not None:
        rows = rows * jnp.asarray(weights, table.dtype)[:, None]
    return jax.ops.segment_sum(rows, seg, num_segments=n_bags)


class TestEmbeddingBag:
    @pytest.mark.parametrize("rows,dim,lookups,bags", [
        (50, 8, 40, 10), (200, 128, 300, 32), (10, 16, 5, 8),
    ])
    def test_matches_ref(self, rows, dim, lookups, bags):
        rng = np.random.default_rng(rows)
        table = jnp.asarray(rng.standard_normal((rows, dim)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, rows, lookups), jnp.int32)
        seg = jnp.asarray(rng.integers(0, bags, lookups), jnp.int32)
        got = _bag(table, idx, seg, bags)
        want = embedding_bag_ref(table, idx, seg, bags)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_weights(self):
        rng = np.random.default_rng(5)
        table = jnp.asarray(rng.standard_normal((20, 4)).astype(np.float32))
        idx = jnp.asarray([1, 2, 3, 1], jnp.int32)
        seg = jnp.asarray([0, 0, 1, 2], jnp.int32)
        w = jnp.asarray([0.5, 2.0, 1.0, -1.0])
        got = _bag(table, idx, seg, 3, weights=w)
        want = embedding_bag_ref(table, idx, seg, 3, weights=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    @pytest.mark.parametrize("rows,dim,lookups", [
        (50, 602, 37),    # reddit width: lanes padded 602 -> 640
        (81, 128, 64),
        (9, 5, 3),        # fewer lookups than one kernel step
    ])
    def test_gather_layout_bit_equal(self, rows, dim, lookups):
        """The (R, 1, D_pad) DMA-gather layout returns table[idx] exactly."""
        rng = np.random.default_rng(rows * dim)
        table = rng.standard_normal((rows, dim)).astype(np.float32)
        idx = rng.integers(0, rows, lookups)
        got = np.asarray(gather_rows(gather_layout(table), idx, dim))
        assert got.shape == (lookups, dim)
        assert np.array_equal(got, table[idx])

    def test_empty_bags_zeroed(self):
        table = jnp.ones((5, 4))
        idx = jnp.asarray([0, 1], jnp.int32)
        seg = jnp.asarray([0, 3], jnp.int32)
        got = _bag(table, idx, seg, 5)
        np.testing.assert_allclose(np.asarray(got[1]), 0.0)
        np.testing.assert_allclose(np.asarray(got[2]), 0.0)
        np.testing.assert_allclose(np.asarray(got[4]), 0.0)
        np.testing.assert_allclose(np.asarray(got[0]), 1.0)
