"""The chip's compiler, asked without the chip: every device program of
the main path, at its product shape, is lowered and compiled for a
described (not attached) TPU v5e 2x2 — what the compiler refuses here it
would refuse on the chip, at no chip time.

Nothing runs, so nothing here says a result is right or fast. The
topology is described inside a fixture (never at import: under several
xdist workers only the worker that runs this file may load the TPU
library), and everything compiles in this process. x64 is switched off
around the compiles — the programs a user's chip run builds are f32
(utils/dtypes.preferred_float), not the f64 the CPU tests otherwise
use — and so is the persistent compile cache, which cannot read back
what is compiled for a device that is not there.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

SHARD = 10_000_000  # commands/depth.py STEP
SEGS = 1 << 21      # 30x of 150 bp reads over one shard, bucketed


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler brings its own thread pool; held to one core
    # (threads inherit the affinity of the thread that starts them)
    # this file loads the machine like any other test worker, and the
    # wall-clock-sensitive tests running beside it keep their cores
    cores = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {min(cores)})
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        os.sched_setaffinity(0, cores)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()
    os.sched_setaffinity(0, cores)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The 2x2 (data, seq) mesh parallel/mesh.make_mesh builds on four
    chips, from the described devices."""
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "seq"))


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def like(sharding, *arrays):
    return [spec(sharding, a.shape, a.dtype) for a in arrays]


def compiled(fn, *args, **static):
    """Lower + compile ``fn`` (a jit, or a plain function jitted here
    with ``static`` as its static arguments) for the shardings its
    argument specs carry."""
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn, static_argnames=tuple(static))
    exe = fn.lower(*args, **static).compile()
    assert exe.memory_analysis() is not None
    return exe


def scalars(sharding, n):
    return [spec(sharding, (), jnp.int32)] * n


# ----------------------------------------------------- depth (one chip)

DEPTH_VARIANTS = {
    # name -> dtypes of the three segment arguments
    "shard_depth_pipeline": (jnp.int32, jnp.int32, jnp.bool_),
    "shard_depth_pipeline_cls_packed": (jnp.int32, jnp.int32, jnp.bool_),
    "shard_depth_pipeline_packed": (jnp.uint16, jnp.uint16, None),
    "shard_depth_pipeline_packed_cls_packed":
        (jnp.uint16, jnp.uint16, None),
}


@pytest.mark.parametrize("window,name", [
    (500, "shard_depth_pipeline"),
    (500, "shard_depth_pipeline_cls_packed"),
    (500, "shard_depth_pipeline_packed"),
    # the depth command's own path at its default 250 bp windows
    (250, "shard_depth_pipeline_packed_cls_packed"),
])
def test_depth_pipeline_product_shape(one_chip, window, name):
    from goleft_tpu.ops import depth_pipeline as dp

    a, b, c = DEPTH_VARIANTS[name]
    segs = [spec(one_chip, (SEGS,), a), spec(one_chip, (SEGS,), b),
            spec(one_chip, (SEGS,), c) if c is not None
            else spec(one_chip, (), jnp.int32)]  # packed wire: base
    compiled(getattr(dp, name), *segs, *scalars(one_chip, 6),
             length=SHARD, window=window)


@pytest.mark.parametrize("batch,bucket,length,window", [
    (1, 64, 256, 256),              # what serve warms at start
    (1, 1 << 17, 1_000_000, 500),   # one 1 Mb /v1/depth region at 30x
])
def test_serve_batched_depth(one_chip, batch, bucket, length, window):
    from goleft_tpu.commands.depth import _batched_cls_packed

    segs = [spec(one_chip, (batch, bucket), t)
            for t in (jnp.int32, jnp.int32, jnp.bool_)]
    compiled(_batched_cls_packed(), *segs, *scalars(one_chip, 6),
             length=length, window=window)


def test_pallas_depth(one_chip):
    """The one Pallas kernel in the tree, at one shard's tiling."""
    from goleft_tpu.ops.pallas_coverage import TILE, pallas_depth

    n_tiles = 1_000_448 // TILE
    tiled = spec(one_chip, (n_tiles, 256), jnp.int32)
    exe = compiled(pallas_depth, tiled, tiled, n_tiles=n_tiles)
    assert "tpu_custom_call" in exe.as_text()


# ------------------------------------------ indexcov / emdepth / cohort

def test_indexcov_chrom_qc(one_chip):
    from goleft_tpu.ops.indexcov_ops import chrom_qc

    compiled(chrom_qc, spec(one_chip, (500, 16384), jnp.float32),
             spec(one_chip, (500, 16384), jnp.bool_),
             spec(one_chip, (), jnp.int32))


def test_indexcov_normalize_across_samples(one_chip):
    """Its device half: the per-sample scan over the host's f64
    per-bin scalars."""
    from goleft_tpu.cohort.streaming import apply_normalization

    compiled(apply_normalization,
             spec(one_chip, (500, 16384), jnp.float32),
             spec(one_chip, (500,), jnp.int32),
             spec(one_chip, (16384,), jnp.float32),
             spec(one_chip, (16384,), jnp.bool_))


def test_indexcov_pca_project(one_chip):
    """indexcov's SVD projection at the README cohort: 30 samples by
    a whole genome of 16 kb bins."""
    from goleft_tpu.ops.indexcov_ops import _pca_project_jit

    compiled(_pca_project_jit,
             spec(one_chip, (30, 190_000), jnp.float32), k=5)


def test_emdepth_em_chunk(one_chip):
    """One EM_CHUNK of windows at 1000-Genomes width, f32 as on the
    chip: the EM, then the copy-number assignment."""
    from goleft_tpu.commands.emdepth_cmd import EM_CHUNK
    from goleft_tpu.models.emdepth import cn_batch, em_depth_batch

    d = spec(one_chip, (EM_CHUNK, 2504), jnp.float32)
    exes = [compiled(em_depth_batch, d)]
    lam = jax.eval_shape(em_depth_batch, d)
    assert lam.dtype == jnp.float32
    exes.append(compiled(cn_batch, spec(one_chip, lam.shape, lam.dtype), d))
    # no gather: the λ table is read by selects (test_emdepth_kernels.py)
    for exe in exes:
        assert " gather(" not in exe.as_text()


def test_cohort_pca_gram_step(one_chip):
    from goleft_tpu.cohort.pca import _chunk_gram

    compiled(_chunk_gram, spec(one_chip, (256, 190_000), jnp.float32),
             spec(one_chip, (190_000,), jnp.float32),
             spec(one_chip, (190_000, 5), jnp.float32))


def test_cohort_pca_sharded_gram_step(mesh4):
    """The shard_map + psum Gram step over all four chips."""
    from goleft_tpu.cohort.pca import _sharded_gram_fn

    mesh = Mesh(mesh4.devices.reshape(4), ("data",))
    rep = NamedSharding(mesh, P())
    exe = compiled(
        _sharded_gram_fn(mesh),
        spec(NamedSharding(mesh, P("data", None)), (256, 190_000),
             jnp.float32),
        spec(rep, (190_000,), jnp.float32),
        spec(rep, (190_000, 5), jnp.float32))
    assert "all-reduce" in exe.as_text()


# ----------------------------------------- pairhmm / rANS / swalign

@pytest.mark.parametrize("r_pad,h_pad", [(160, 320), (256, 512)])
def test_pairhmm_forward_bucket(one_chip, r_pad, h_pad):
    from goleft_tpu.ops import pairhmm as ph

    b = 64
    read = np.zeros(r_pad, np.uint8)
    packed = ph._pack_bucket(
        list(range(b)), [read] * b, [np.full(r_pad, 1e-3)] * b,
        [np.zeros(h_pad, np.uint8)] * b, r_pad, h_pad, np.float32)
    trans = ph.transition_probs().astype(np.float32)
    compiled(ph._forward_bucket_impl, *like(one_chip, *packed, trans),
             rescale=True)


class _Captured(Exception):
    pass


class _CaptureJit:
    """Stands in for the shared rANS jit: keeps the call's arguments."""

    def _cache_size(self):
        return 0

    def __call__(self, *args, **static):
        raise _Captured(args, static)


@pytest.mark.parametrize("order", [0, 1])
def test_rans_scan_lanes(one_chip, monkeypatch, order):
    """The vmapped rANS-Nx16 scan over a bucket of 64 KiB blocks, at
    the geometry the product's own bucketing gives them."""
    from goleft_tpu.io import rans_nx16 as rx
    from goleft_tpu.ops import rans_device as rd

    monkeypatch.setattr(rd, "_jitted", _CaptureJit)
    rng = np.random.default_rng(order)
    blocks = [bytes(rng.choice([65, 67, 71, 84], p=[.4, .3, .2, .1],
                               size=65536).astype(np.uint8))
              for _ in range(8)]
    encs = [rx.encode(b, order=order, x32=True) for b in blocks]
    with pytest.raises(_Captured) as got:
        rd.decode_streams(encs, [len(b) for b in blocks])
    args, static = got.value.args
    assert static["order1"] == bool(order)
    compiled(rd._decode_bucket_impl, *like(one_chip, *args), **static)


def test_swalign_bucket(one_chip):
    from goleft_tpu.ops import swalign as sw

    b, r1, w = 256, 161, 256  # 160 bp reads against 256 bp windows
    compiled(sw._sw_bucket_impl,
             spec(one_chip, (b, r1), jnp.uint8),
             spec(one_chip, (b,), jnp.int32),
             spec(one_chip, (b, w), jnp.uint8),
             spec(one_chip, (b,), jnp.int32),
             spec(one_chip, (4,), jnp.int32))


# ------------------------------------------------------- four chips

@pytest.mark.parametrize("carry_mode,collective", [
    # the gather of one carry per shard reaches the chip as an
    # all-reduce: the TPU compiler's own choice for so small a gather
    ("all_gather", "all-reduce"), ("scan", "collective-permute")])
def test_sharded_depth_fn(mesh4, carry_mode, collective):
    """The sequence-parallel coverage kernel: 4 samples by 2 x 2.5 Mb
    (it has no CLI caller, so no product shape of its own)."""
    from goleft_tpu.parallel.sharded_coverage import sharded_depth_fn

    shard_len, per_shard = 2_500_000, 1 << 19
    sh = NamedSharding(mesh4, P("data", "seq"))
    fn = sharded_depth_fn(mesh4, shard_len, 500, carry_mode=carry_mode)
    args = [spec(sh, (4, 2 * per_shard), t)
            for t in (jnp.int32, jnp.int32, jnp.bool_)]
    assert collective in compiled(fn, *args).as_text()


def test_cohortdepth_sample_sharded(mesh4):
    """cohortdepth --engine device on four chips: 8 samples split over
    the devices, one 10 Mb shard each pass, no collectives."""
    from goleft_tpu.commands.cohortdepth import _batched_pipeline

    mesh = Mesh(mesh4.devices.reshape(4), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    rep = NamedSharding(mesh, P())
    segs = [spec(sh, (8, 1 << 20), t)
            for t in (jnp.int32, jnp.int32, jnp.bool_)]
    exe = compiled(
        functools.partial(_batched_pipeline, length=SHARD, window=500),
        *segs, *scalars(rep, 4))
    out, = jax.tree.leaves(exe.output_shardings)
    assert len(out.device_set) == 4
