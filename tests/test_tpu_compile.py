"""Compile the Pallas selection kernels and the paper-lstm train step for
a described (not attached) TPU v5e.

Interpret-mode tests cannot see what the chip's compiler refuses —
illegal block tiling, scalar stores to VMEM, unsupported primitives, too
much fast memory. These compile every kernel at published-width sizes
with ``interpret=False`` and check that the lowered program holds the
Mosaic kernel (``tpu_custom_call``), and check which collectives carry
the sparse messages on a 4-chip mesh. Nothing runs, so they say nothing
about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import TrainConfig, get_config
from repro.core import arena
from repro.kernels import ops, ref
from repro.kernels import segmented as kseg
from repro.models.registry import get_model
from repro.train.trainer import make_gradient_sync, make_train_step

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _geometry(sizes):
    return arena.build_group(
        0, "threshold_bsearch", "float32",
        [(i, f"l{i}", n, max(1, n // 100), 2 * max(1, n // 100), 1)
         for i, n in enumerate(sizes)]).geometry


def _paper_lstm_leaf_sizes():
    shapes = jax.eval_shape(get_model(get_config("paper-lstm")).init_params)
    return [int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)]


# One arena holding every paper-lstm leaf (~66M f32), and internlm2-1.8b's
# [92544, 2048] vocabulary leaf as an arena of its own.
ARENAS = {
    "paper-lstm": _paper_lstm_leaf_sizes,
    "internlm2-vocab": lambda: [92544 * 2048],
}


def _seg_call(kernel, geom, stride_b):
    block_seg, n = geom.block_seg, geom.n_seg
    if kernel == "abs_sum_max":
        return lambda x, t: kseg.seg_abs_sum_max(x, block_seg, n,
                                                 interpret=False)
    if kernel == "abs_sum_max_strided":
        return lambda x, t: kseg.seg_abs_sum_max(
            x, block_seg, n, stride_b=stride_b, interpret=False)
    if kernel == "count_gt":
        return lambda x, t: kseg.seg_count_gt(x, block_seg, t,
                                              interpret=False)
    if kernel == "count_gt_strided":
        return lambda x, t: kseg.seg_count_gt(
            x, block_seg, t, stride_b=stride_b, interpret=False)
    if kernel == "compact_gt":
        cap = kseg._cap_for(2 * max(geom.seg_ks), geom.nblocks, geom.block)
        return lambda x, t: kseg.seg_compact_gt(
            x, block_seg, geom.block_base, geom.block_size, t, cap,
            interpret=False)
    return lambda x, t: kseg.seg_residual_update_stats(
        x.astype(jnp.bfloat16), x, x, x, block_seg, n, momentum=0.9,
        nesterov=True, weight_decay=1e-4, round_dtype=jnp.bfloat16,
        interpret=False)


@pytest.mark.parametrize("kernel", [
    "abs_sum_max", "abs_sum_max_strided", "count_gt", "count_gt_strided",
    "compact_gt", "residual_update_stats"])
@pytest.mark.parametrize("arena_name", list(ARENAS))
def test_segmented_kernel_compiles(one_chip, kernel, arena_name):
    geom = _geometry(ARENAS[arena_name]())
    stride_b = np.full(geom.nblocks, 16, np.int32)
    x = jax.ShapeDtypeStruct((geom.nblocks, geom.block), jnp.float32,
                             sharding=one_chip)
    t = jax.ShapeDtypeStruct((geom.n_seg,), jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(_seg_call(kernel, geom, stride_b), x, t))


def test_bsearch_compaction_fits_in_bucket_temporaries(one_chip):
    """The paper-lstm arena's bsearch ``multi_select`` (jnp backend,
    statistics given, as the fused accumulate hands them over) compiles
    for one chip with no more temporary bytes than the bucket compaction
    it replaced takes alone at the same width: no scatter, no per-slot
    branch, and ``peak_hbm_gb`` cannot grow unseen."""
    geom = _geometry(_paper_lstm_leaf_sizes())
    x = jax.ShapeDtypeStruct((geom.nblocks, geom.block), jnp.float32,
                             sharding=one_chip)
    vec = jax.ShapeDtypeStruct((geom.n_seg,), jnp.float32,
                               sharding=one_chip)
    spec = kseg.SegmentSpec(alg="bsearch", eps=1e-3)
    select = _compile(lambda x, m, mx: kseg.multi_select(
        [(x, geom, spec, (m, mx))], use_pallas=False), x, vec, vec)
    cap = max(kseg._cap_for(2 * k, r1 - r0, geom.block)
              for k, (r0, r1) in zip(geom.seg_ks, geom.seg_rows))
    buckets = _compile(lambda x, t: ref.seg_compact_gt(
        x, geom.block_seg, geom.block_base, geom.block_size, t, cap), x, vec)
    lines = select.as_text().splitlines()
    # the only scatters left are the search's per-segment counts
    assert all(f"[{geom.n_seg}]" in line.split(" scatter(")[0]
               for line in lines if " scatter(" in line)
    assert not [line for line in lines if " conditional(" in line]
    assert (select.memory_analysis().temp_size_in_bytes
            <= buckets.memory_analysis().temp_size_in_bytes)


LEAF_ROWS, LEAF_BLOCK = 15000, 1024     # the paper-lstm embedding leaf


@pytest.mark.parametrize("kernel", [
    "abs_sum_max", "count_gt", "compact_gt", "residual_update"])
def test_per_leaf_kernel_compiles(one_chip, kernel):
    n = LEAF_ROWS * LEAF_BLOCK
    x2d = jax.ShapeDtypeStruct((LEAF_ROWS, LEAF_BLOCK), jnp.float32,
                               sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    flat = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    if kernel == "abs_sum_max":
        compiled = _compile(lambda x: ops.abs_sum_max(x, interpret=False),
                            x2d)
    elif kernel == "count_gt":
        compiled = _compile(
            lambda x, t: ops.count_gt(x, t, interpret=False), x2d, t)
    elif kernel == "compact_gt":
        cap = ops._bucket_cap(n // 100, LEAF_ROWS, LEAF_BLOCK)
        compiled = _compile(
            lambda x, t: ops.compact_gt(x, t, cap, n, interpret=False),
            x2d, t)
    else:
        compiled = _compile(
            lambda g, u, v: ops.residual_update(
                g, u, v, momentum=0.9, nesterov=False, interpret=False),
            flat, flat, flat)
    _assert_kernel(compiled)


def test_paper_lstm_pallas_step_compiles(one_chip, monkeypatch):
    """The whole jitted paper-lstm step (published widths, PTB batch
    20 x 35, momentum+clip(threshold_bsearch) at density 0.01) with the
    Pallas selection backend compiles for one chip and fits its HBM.

    ``jax.default_backend()`` is the CPU here, which would interpret the
    kernels; the test steers the kernels to their compiled form."""
    monkeypatch.setattr(kseg, "resolve_interpret",
                        lambda interpret: bool(interpret))
    cfg = get_config("paper-lstm")
    tc = TrainConfig(optimizer="momentum+clip(threshold_bsearch)",
                     density=0.01, backend="pallas")
    model = get_model(cfg)
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                           sharding=one_chip)
    params = jax.eval_shape(model.init_params)
    state = jax.eval_shape(make_gradient_sync(tc, None).init, params)
    batch = model.train_inputs(20, 35)
    step = make_train_step(model, None, None, tc, donate=False)
    compiled = step.lower(
        jax.tree.map(place, params), jax.tree.map(place, state),
        jax.tree.map(place, batch),
        place(jax.ShapeDtypeStruct((), jnp.float32))).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


def test_rgc_message_crosses_the_mesh_as_int32(topo):
    """On a 4-chip ("data",) mesh the packed sparse messages (f32 values
    with bitcast-int32 counts and indices) must cross as int32: XLA:TPU
    may lower the all-gather to a sum with zeros, which flushes the
    denormal f32 views of the indices to zero. Only the scalar loss mean
    may travel as f32."""
    import re

    from jax.sharding import Mesh
    mesh = Mesh(np.array(topo.devices), ("data",))
    cfg = get_config("paper-lstm", smoke=True)
    tc = TrainConfig(optimizer="momentum+clip(threshold_bsearch)",
                     density=0.01, transport="fused_allgather")
    model = get_model(cfg)
    params = jax.eval_shape(model.init_params)
    state = jax.eval_shape(make_gradient_sync(tc, mesh).init, params)
    step = make_train_step(model, mesh, None, tc, donate=False)
    text = step.lower(params, state, model.train_inputs(8, 16),
                      jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    results = [line.split(" all-")[0] for line in text.splitlines()
               if re.search(r"\sall-(gather|reduce)(-start)?\(", line)]
    assert any("s32[" in r for r in results), results
    assert not any(re.search(r"f32\[\d", r) for r in results), results
