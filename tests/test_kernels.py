"""Pallas kernels vs pure-jnp oracles (ref.py): shape/dtype sweeps,
interpret=True on CPU (TPU is the lowering target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import selection as sel
from repro.kernels import ops, ref
from repro.kernels.ops import abs_sum_max, compact_gt, count_gt

SHAPES = [(4, 128), (8, 256), (3, 1024), (16, 512), (1, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _x2d(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


class TestResolveInterpret:
    """Interpret only on CPU, compile on TPU, refuse anything else."""

    @pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
    def test_by_backend(self, monkeypatch, backend, want):
        from repro.kernels import segmented as kseg
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert kseg.resolve_interpret(None) is want
        assert kseg.resolve_interpret(not want) is (not want)

    def test_other_backend_raises(self, monkeypatch):
        from repro.kernels import segmented as kseg
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="target TPU"):
            kseg.resolve_interpret(None)


class TestBlockStats:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_abs_sum_max(self, shape, dtype):
        x = _x2d(shape, dtype)
        s, m = abs_sum_max(x, interpret=True)
        s_ref, m_ref = ref.abs_sum_max(x)
        np.testing.assert_allclose(s, s_ref, rtol=2e-2 if dtype == jnp.bfloat16
                                   else 1e-5)
        np.testing.assert_allclose(m, m_ref, rtol=1e-6)


class TestCountGt:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("thr", [0.0, 0.5, 1.5, 10.0])
    def test_count(self, shape, thr):
        x = _x2d(shape, jnp.float32, seed=shape[1])
        got = count_gt(x, jnp.float32(thr), interpret=True)
        want = ref.count_gt(x, jnp.float32(thr))
        assert int(got) == int(want)


class TestCompactGt:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_against_oracle(self, shape):
        nb, block = shape
        n = nb * block
        x = _x2d((n,), jnp.float32, seed=n)
        thr = jnp.float32(1.0)
        cap = 32
        vals, idx, counts = compact_gt(x.reshape(nb, block), thr, cap, n,
                                       interpret=True)
        v_ref, i_ref, c_ref = ref.compact_gt(x, thr, block, cap)
        np.testing.assert_array_equal(counts, c_ref)
        np.testing.assert_array_equal(idx, i_ref)
        np.testing.assert_allclose(vals, v_ref)

    def test_partial_final_block(self):
        """n not a multiple of block: padding indices must be == n."""
        n, block, cap = 300, 128, 16
        x = _x2d((n,), jnp.float32, seed=1)
        x2, _ = ops._to2d(x, block)
        vals, idx, counts = compact_gt(x2, jnp.float32(0.8), cap, n,
                                       interpret=True)
        flat = np.asarray(idx).reshape(-1)
        assert np.all((flat < n) | (flat == n))


class TestResidualUpdate:
    @pytest.mark.parametrize("momentum", [0.0, 0.9])
    @pytest.mark.parametrize("nesterov", [False, True])
    @pytest.mark.parametrize("shape", [(256,), (33, 17), (4, 8, 16)])
    def test_fused_update(self, momentum, nesterov, shape):
        rng = np.random.default_rng(3)
        g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        u_new, v_new = ops.residual_update(g, u, v, momentum=momentum,
                                           nesterov=nesterov)
        u_ref, v_ref = ref.residual_update(g, u, v, momentum=momentum,
                                           nesterov=nesterov)
        np.testing.assert_allclose(u_new, u_ref, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(v_new, v_ref, rtol=1e-5, atol=1e-6)


class TestGoldenEdgeShapes:
    """All four kernels vs their ref.py oracles on the edge geometry the
    shape sweeps above skip: non-block-multiple lengths, all-zero input,
    all-survivor input, and single-element leaves."""

    # flat length, block — chosen so the final block is partial (300/128),
    # a single element (1/128) or exactly one full block (128/128)
    EDGE = [(300, 128), (1, 128), (127, 128), (129, 128), (128, 128)]

    @staticmethod
    def _flat(n, kind, seed=5):
        if kind == "zeros":
            return jnp.zeros((n,), jnp.float32)
        rng = np.random.default_rng(seed)
        x = rng.standard_normal(n).astype(np.float32)
        if kind == "survivors":
            # every element clears a 0.5 threshold
            x = np.sign(x) * (np.abs(x) + 1.0)
        return jnp.asarray(x)

    @pytest.mark.parametrize("n,block", EDGE)
    @pytest.mark.parametrize("kind", ["normal", "zeros", "survivors"])
    def test_block_stats_golden(self, n, block, kind):
        x = self._flat(n, kind)
        x2d, _ = ops._to2d(x, block)
        s, m = abs_sum_max(x2d, interpret=True)
        s_ref, m_ref = ref.abs_sum_max(x)       # zero padding adds nothing
        np.testing.assert_allclose(s, s_ref, rtol=1e-6)
        np.testing.assert_allclose(m, m_ref, rtol=1e-6)

    @pytest.mark.parametrize("n,block", EDGE)
    @pytest.mark.parametrize("kind", ["normal", "zeros", "survivors"])
    def test_count_gt_golden(self, n, block, kind):
        x = self._flat(n, kind)
        x2d, _ = ops._to2d(x, block)
        for thr in (0.0, 0.5, 100.0):
            got = count_gt(x2d, jnp.float32(thr), interpret=True)
            want = ref.count_gt(x, jnp.float32(thr))
            assert int(got) == int(want), (n, block, kind, thr)
        if kind == "survivors":
            assert int(count_gt(x2d, jnp.float32(0.5), interpret=True)) == n

    @pytest.mark.parametrize("n,block", EDGE)
    @pytest.mark.parametrize("kind", ["normal", "zeros", "survivors"])
    def test_compact_gt_golden(self, n, block, kind):
        """Including bucket overflow: all-survivor input with cap < block
        drops overflow identically in kernel and oracle."""
        x = self._flat(n, kind)
        x2d, _ = ops._to2d(x, block)
        for cap in (8, 32):
            vals, idx, counts = compact_gt(x2d, jnp.float32(0.5), cap, n,
                                           interpret=True)
            v_ref, i_ref, c_ref = ref.compact_gt(x, jnp.float32(0.5),
                                                 block, cap)
            np.testing.assert_array_equal(counts, c_ref)
            np.testing.assert_array_equal(idx, i_ref)
            np.testing.assert_allclose(vals, v_ref)
            # padding contract: indices are in range or == sentinel (n)
            flat = np.asarray(idx).reshape(-1)
            assert np.all((flat < n) | (flat == n))

    @pytest.mark.parametrize("shape", [(1,), (300,), (1, 1), (127,)])
    @pytest.mark.parametrize("kind", ["normal", "zeros"])
    def test_residual_update_golden(self, shape, kind):
        n = int(np.prod(shape))
        g = self._flat(n, kind).reshape(shape)
        rng = np.random.default_rng(9)
        u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for momentum, nesterov in ((0.0, False), (0.9, False), (0.9, True)):
            u_new, v_new = ops.residual_update(g, u, v, momentum=momentum,
                                               nesterov=nesterov)
            u_ref, v_ref = ref.residual_update(g, u, v, momentum=momentum,
                                               nesterov=nesterov)
            np.testing.assert_allclose(u_new, u_ref, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(v_new, v_ref, rtol=1e-5, atol=1e-6)


class TestKernelSelectors:
    """ops.py composite selectors must agree with core/selection.py."""

    @pytest.mark.parametrize("n,k", [(1000, 5), (5000, 13), (20000, 20)])
    def test_trimmed_topk_matches_jnp(self, n, k):
        x = _x2d((n,), jnp.float32, seed=n)
        got = ops.trimmed_topk(x, k)
        want = sel.trimmed_topk(x, k)
        assert set(map(int, got.indices)) == set(map(int, want.indices))
        got_vals = sorted(map(float, got.values))
        want_vals = sorted(map(float, want.values))
        np.testing.assert_allclose(got_vals, want_vals, rtol=1e-6)

    @pytest.mark.parametrize("n,k", [(1000, 5), (8192, 16)])
    def test_bsearch_matches_jnp(self, n, k):
        x = _x2d((n,), jnp.float32, seed=n + 1)
        got, thr_g = ops.threshold_binary_search(x, k)
        want, thr_w = sel.threshold_binary_search(x, k)
        np.testing.assert_allclose(thr_g, thr_w, rtol=1e-5)
        assert int(got.count) == int(want.count)
        c = int(got.count)
        assert (set(map(int, np.asarray(got.indices)[:c]))
                == set(map(int, np.asarray(want.indices)[:c])))

    def test_rgc_pallas_backend_end_to_end(self):
        """rgc_apply(backend='pallas') produces the same update as jnp."""
        from repro.core.rgc import RGCConfig, rgc_apply, rgc_init
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.standard_normal((600, 70)),
                                   jnp.float32)}
        grads = {"w": jnp.asarray(rng.standard_normal((600, 70)),
                                  jnp.float32)}
        outs = {}
        for backend in ("jnp", "pallas"):
            cfg = RGCConfig(density=0.001, sync_axes=(), backend=backend,
                            dense_threshold_bytes=1024)
            state = rgc_init(params, cfg)
            new_p, _ = rgc_apply(grads, params, state, lr=jnp.float32(0.1),
                                 cfg=cfg)
            outs[backend] = np.asarray(new_p["w"])
        np.testing.assert_allclose(outs["jnp"], outs["pallas"], rtol=1e-6)


# ---------------------------------------------------------------------------
# segmented (flat-arena) kernels vs their jnp twins
# ---------------------------------------------------------------------------

def _arena(sizes, seed=0):
    """Block-aligned arena [nb, 1024] + geometry for the given slot sizes."""
    from repro.core import arena as A
    group = A.build_group(
        0, "trimmed_topk", "float32",
        [(i, f"l{i}", n, max(1, n // 100), max(1, n // 100),
          1 + 2 * max(1, n // 100)) for i, n in enumerate(sizes)])
    rng = np.random.default_rng(seed)
    arrs = [jnp.asarray(rng.standard_normal(n), jnp.float32) for n in sizes]
    return A.gather(group, arrs), group.geometry, arrs


SEG_CASES = [
    [1000],                       # single slot
    [1023, 1025, 7],              # non-block-multiple mix
    [2048, 1, 5000],              # single-element slot
    [64, 64, 64, 64],             # several tiny slots
]


class TestSegmentedKernels:
    @pytest.mark.parametrize("sizes", SEG_CASES)
    def test_seg_abs_sum_max(self, sizes):
        from repro.kernels import segmented as kseg
        x2d, geom, arrs = _arena(sizes)
        s, m = kseg.seg_abs_sum_max(x2d, geom.block_seg, geom.n_seg,
                                    interpret=True)
        s_ref, m_ref = ref.seg_abs_sum_max(x2d, geom.block_seg,
                                           geom.block_size, geom.n_seg)
        np.testing.assert_allclose(s, s_ref, rtol=1e-6)
        np.testing.assert_array_equal(m, m_ref)
        # and against the per-leaf selector statistics
        for i, a in enumerate(arrs):
            np.testing.assert_array_equal(m[i], jnp.max(jnp.abs(a)))

    @pytest.mark.parametrize("sizes", SEG_CASES)
    @pytest.mark.parametrize("thr", [0.0, 0.5, 2.0])
    def test_seg_count_gt(self, sizes, thr):
        from repro.kernels import segmented as kseg
        x2d, geom, arrs = _arena(sizes, seed=3)
        thrs = jnp.full((geom.n_seg,), thr, jnp.float32)
        got = kseg.seg_count_gt(x2d, geom.block_seg, thrs, interpret=True)
        want = ref.seg_count_gt(x2d, geom.block_seg, thrs, geom.n_seg)
        np.testing.assert_array_equal(got, want)
        # per-segment counts match the per-leaf count over the slot
        # (identical zero padding on both sides)
        for i, a in enumerate(arrs):
            pad = (-a.size) % 1024
            assert int(got[i]) == int(
                jnp.sum(jnp.abs(jnp.pad(a, (0, pad))) > thr))

    @pytest.mark.parametrize("sizes", SEG_CASES)
    def test_seg_compact_gt(self, sizes):
        from repro.kernels import segmented as kseg
        x2d, geom, arrs = _arena(sizes, seed=7)
        thrs = jnp.full((geom.n_seg,), 0.8, jnp.float32)
        cap = 16
        g = kseg.seg_compact_gt(x2d, geom.block_seg, geom.block_base,
                                geom.block_size, thrs, cap, interpret=True)
        w = ref.seg_compact_gt(x2d, geom.block_seg, geom.block_base,
                               geom.block_size, thrs, cap)
        np.testing.assert_array_equal(g[2], w[2])     # counts
        np.testing.assert_array_equal(g[1], w[1])     # local indices
        np.testing.assert_allclose(g[0], w[0])        # values
        # indices are slot-LOCAL with padding == slot size; padding in
        # the arena (beyond each slot's size) is never selected
        for s_ord, (r0, r1) in enumerate(geom.seg_rows):
            size = geom.seg_sizes[s_ord]
            idx = np.asarray(g[1][r0:r1])
            assert np.all(idx <= size)

    @pytest.mark.parametrize("momentum,nesterov,wd",
                             [(0.9, False, 0.0), (0.9, True, 0.0),
                              (0.0, False, 0.0), (0.9, False, 0.01)])
    def test_seg_residual_update_stats(self, momentum, nesterov, wd):
        from repro.kernels import segmented as kseg
        sizes = [1023, 300, 2048]
        x2d, geom, _ = _arena(sizes, seed=9)
        g2d, _, _ = _arena(sizes, seed=10)
        u2d, _, _ = _arena(sizes, seed=11)
        p2d, _, _ = _arena(sizes, seed=12)
        got = kseg.seg_residual_update_stats(
            g2d, x2d, u2d if momentum else None, p2d if wd else None,
            geom.block_seg, geom.n_seg, momentum=momentum,
            nesterov=nesterov, weight_decay=wd, interpret=True)
        want = ref.seg_residual_update_stats(
            g2d, x2d, u2d if momentum else None, p2d if wd else None,
            geom.block_seg, geom.n_seg, momentum=momentum,
            nesterov=nesterov, weight_decay=wd)
        # the fused kernel may FMA-contract the momentum product
        # (documented fuse_accumulate caveat): allow last-ulp noise
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6,
                                   atol=1e-6)              # V'
        if momentum:
            np.testing.assert_allclose(got[1], want[1], rtol=1e-6,
                                       atol=1e-6)          # U'
        else:
            assert got[1] is None and want[1] is None
        np.testing.assert_allclose(got[2], want[2], rtol=1e-5)  # sums
        np.testing.assert_allclose(got[3], want[3], rtol=1e-6)  # maxs

    def test_seg_residual_bf16_round(self):
        from repro.kernels import segmented as kseg
        sizes = [1500]
        x2d, geom, _ = _arena(sizes, seed=20)
        g2d, _, _ = _arena(sizes, seed=21)
        v, _, _, _ = kseg.seg_residual_update_stats(
            g2d, x2d, None, None, geom.block_seg, geom.n_seg,
            momentum=0.0, nesterov=False, round_dtype=jnp.bfloat16,
            interpret=True)
        v = np.asarray(v)
        assert np.array_equal(v, np.asarray(
            jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32)))


def _plant(arrs, slot, at, value):
    a = np.asarray(arrs[slot]).copy()
    a[at] = value
    arrs[slot] = jnp.asarray(a)


def _packed_row():
    # 300 survivors in one row of a slot whose old per-row bucket held
    # 88 (the case that fell back), a few spread over the other slot
    from repro.core import arena as A
    _, _, arrs = _arena([100_000, 4096], seed=41)
    _plant(arrs, 0, slice(7 * 1024 + 3, 7 * 1024 + 303), -9.0)
    _plant(arrs, 1, [0, 1023, 1024, 4095], 9.0)
    group = A.build_group(0, "threshold_bsearch", "float32",
                          [(i, f"l{i}", a.size, a.size // 100,
                            a.size // 50, 1) for i, a in enumerate(arrs)])
    assert _bucket_cap(group.geometry, 0) < 300
    return [(A.gather(group, arrs), group.geometry)], [[6.0, 6.0]]


def _bucket_cap(geom, s):
    """The per-row bucket the bucket compaction gives slot ``s``."""
    from repro.kernels import segmented as kseg
    r0, r1 = geom.seg_rows[s]
    return kseg._cap_for(2 * geom.seg_ks[s], r1 - r0, geom.block)


def _one(sizes, seed, thr):
    x2d, geom, _ = _arena(sizes, seed=seed)
    return [(x2d, geom)], [thr]


def _ragged():
    # nonzero lanes past each slot's size: never selected
    x2d, geom, _ = _arena([1023, 1025, 7, 5000], seed=43)
    x = np.asarray(x2d).reshape(-1).copy()
    for (r0, r1), size in zip(geom.seg_rows, geom.seg_sizes):
        x[r0 * 1024 + size:r1 * 1024] = 50.0
    return [(jnp.asarray(x.reshape(x2d.shape)), geom)], [[2.0] * 4]


def _two_arenas():
    a = _arena([33_001, 500], seed=44)
    b = _arena([4096, 1023, 2048], seed=45)
    return ([(a[0], a[1]), (b[0], b[1])],
            [[2.5, 0.5], [3.0, 100.0, 1.0]])


COMPACT_CASES = {
    "packed_row": _packed_row,
    "padding": lambda: _one([33_001, 4096], 42, [3.0, 3.0]),
    "truncation": lambda: _one([33_001, 4096], 42, [0.5, 0.5]),
    "ragged": _ragged,
    "empty": lambda: _one([4096, 2048], 46, [100.0, 1.0]),
    "two_arenas": _two_arenas,
}


class TestSegmentedSelectors:
    """Segmented selectors vs the per-leaf selectors, slot by slot
    (the bitwise contract the arena pipeline rests on)."""

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_trimmed_matches_per_leaf(self, use_pallas):
        from repro.core.selection import trimmed_topk
        from repro.kernels import segmented as kseg
        sizes = [33_001, 500, 2048]
        x2d, geom, arrs = _arena(sizes, seed=31)
        selected = kseg.trimmed_topk_segments(
            x2d, geom, use_pallas=use_pallas, interpret=True)
        for i, a in enumerate(arrs):
            k = geom.seg_ks[i]
            if use_pallas:
                want = ops.trimmed_topk(a, k, interpret=True)
            else:
                want = trimmed_topk(a, k)
            np.testing.assert_array_equal(selected[i].indices, want.indices)
            np.testing.assert_array_equal(selected[i].values, want.values)
            assert int(selected[i].count) == int(want.count)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_bsearch_matches_per_leaf(self, use_pallas):
        from repro.core.selection import threshold_binary_search
        from repro.kernels import segmented as kseg
        sizes = [33_001, 4096]
        x2d, geom, arrs = _arena(sizes, seed=32)
        sel_list, thr = kseg.threshold_bsearch_segments(
            x2d, geom, use_pallas=use_pallas, interpret=True)
        for i, a in enumerate(arrs):
            k = geom.seg_ks[i]
            if use_pallas:
                want, thr_want = ops.threshold_binary_search(
                    a, k, interpret=True)
            else:
                want, thr_want = threshold_binary_search(a, k)
            np.testing.assert_array_equal(sel_list[i].indices, want.indices)
            np.testing.assert_array_equal(sel_list[i].values, want.values)
            assert int(sel_list[i].count) == int(want.count)
            np.testing.assert_array_equal(thr[i], thr_want)

    @pytest.mark.parametrize("case", list(COMPACT_CASES))
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_bsearch_compaction_is_threshold_filter(self, use_pallas, case):
        """The bsearch compaction at fixed thresholds (threshold reuse,
        no search) is each slot's ``threshold_filter``, bitwise: indices,
        values, count and overflow."""
        from repro.kernels import segmented as kseg
        arenas, thrs = COMPACT_CASES[case]()
        parts = [(x2d, geom, kseg.SegmentSpec(
            alg="bsearch", eps=1e-3, refresh=jnp.zeros(geom.n_seg, bool),
            cached=jnp.asarray(t, jnp.float32)), None)
            for (x2d, geom), t in zip(arenas, thrs)]
        out = kseg.multi_select(parts, use_pallas=use_pallas,
                                interpret=True)
        for (x2d, geom), t, (sel_list, thr) in zip(arenas, thrs, out):
            np.testing.assert_array_equal(thr, np.float32(t))
            for s, got in enumerate(sel_list):
                want = sel.threshold_filter(kseg._slot_flat(x2d, geom, s),
                                            jnp.float32(t[s]),
                                            capacity=2 * geom.seg_ks[s])
                for g, w in zip(got, want):
                    assert g.dtype == w.dtype and g.shape == w.shape
                    np.testing.assert_array_equal(g, w)

