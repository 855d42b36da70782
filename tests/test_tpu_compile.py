"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The interpret-mode tests (test_kernels_cma.py) check the kernels' numbers
on the CPU; they cannot see what Mosaic refuses: an index map returning
i64 under x64, a block that breaks the (8, 128) tiling rule at S > 1, an
op Mosaic cannot legalise, a 64-bit operand.  These tests compile each
kernel for a described (not attached) v5e chip, under the suite's x64,
with the engines' float64 state and several slots, and check that the
compiled program holds the Mosaic kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cma_gen
from repro.kernels.cma_sample import cma_sample
from repro.kernels.cma_update import cma_rank_mu_update

S, LAM = 4, 48


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an AOT compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of any cache the env configures
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield desc
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _cases(n, dt):
    """(kernel, operand shapes) of the main-path kernels at S slots, as
    ``ops`` calls them for state of dtype ``dt``: float64 state never
    reaches the eval-fused kernels (``ops._kernel_sample`` evaluates its
    float64 X instead)."""
    f64, i32, u32 = dt, jnp.int32, jnp.uint32
    wide = jnp.dtype(dt).itemsize > 4
    state = [((S, n), f64), ((S,), f64), ((S, n, n), f64), ((S, n), f64)]
    sep = [((S, n), f64), ((S, n), f64), ((S,), f64), ((S,), i32),
           ((S,), i32)]
    cases = {
        "cma_gen_sample": (cma_gen.cma_gen_sample,
                           state + [((S, LAM, n), f64)]),
        "cma_gen_sample_rng": (functools.partial(cma_gen.cma_gen_sample_rng,
                                                 lam=LAM),
                               state + [((S, 2), u32)]),
        "cma_gen_update": (cma_gen.cma_gen_update,
                           [((S, n, n), f64), ((S, n, n), f64), ((S, n), f64),
                            ((S, n), f64), ((S, n), f64), ((S, LAM, n), f64),
                            ((S, LAM), f64),
                            ((S, len(cma_gen.COEF_FIELDS)), jnp.float32)]),
    }
    if not wide:
        cases["cma_gen_sample_eval"] = (cma_gen.cma_gen_sample_eval,
                                        state + [((S, LAM, n), f64)] + sep)
        cases["cma_gen_sample_rng_eval"] = (
            functools.partial(cma_gen.cma_gen_sample_rng_eval, lam=LAM),
            state + [((S, 2), u32)] + sep)
    return cases


MAIN_PATH = ([("float64", k) for k in ("cma_gen_sample", "cma_gen_sample_rng",
                                       "cma_gen_update")]
             + [("float32", k) for k in ("cma_gen_sample",
                                         "cma_gen_sample_eval",
                                         "cma_gen_sample_rng",
                                         "cma_gen_sample_rng_eval",
                                         "cma_gen_update")])


@pytest.mark.parametrize("n", [40, 256])
@pytest.mark.parametrize("dtype,kernel", MAIN_PATH)
def test_gen_kernel_compiles_for_v5e(one_chip, kernel, n, dtype):
    fn, shapes = _cases(n, jnp.dtype(dtype))[kernel]
    assert "tpu_custom_call" in _compile(fn, *shapes, sharding=one_chip)


@pytest.mark.parametrize("kernel", ["cma_gen_sample", "cma_gen_update"])
def test_member_vmap_folds_into_one_kernel(one_chip, kernel):
    """A campaign vmaps its members over the slot-batched kernels; the
    vmap must fold into the slot axis (one Mosaic kernel over members ×
    slots) rather than go through Pallas's batching rule, whose blocks
    break the tiling rule."""
    members, n = 6, 40
    fn, shapes = _cases(n, jnp.float64)[kernel]
    shapes = [((members,) + s, d) for s, d in shapes]
    text = _compile(jax.vmap(fn), *shapes, sharding=one_chip)
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("n", [40, 256])
def test_per_op_kernels_compile_for_v5e(one_chip, n):
    f64 = jnp.float64
    sample = _compile(cma_sample, ((n,), f64), ((), f64), ((n, n), f64),
                      ((n,), f64), ((LAM, n), f64), sharding=one_chip)
    update = _compile(cma_rank_mu_update, ((n, n), f64), ((LAM, n), f64),
                      ((LAM,), f64), ((n,), f64), ((), f64), ((), f64),
                      ((), f64), sharding=one_chip)
    assert "tpu_custom_call" in sample and "tpu_custom_call" in update


def test_s1_mesh_segment_compiles_for_four_chips(topo, monkeypatch):
    """The S1 (ordered) segment is one shard_map program over the 2x2
    mesh, with the kernels inside and the campaign-global budget and best
    reduced across chips (the TPU lowers only sum all-reduces of f64)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.distributed import mesh_engine
    from repro.fitness import bbob
    from repro.kernels import ops

    # trace the chip's branch of the kernel dispatch (Mosaic, not interpret)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("camp",))
    eng = mesh_engine.MeshCampaignEngine(strategy="ordered", mesh=mesh, n=8,
                                         lam_start=8, kmax_exp=0,
                                         max_evals=2000, impl="pallas")
    keys = jnp.stack([jax.random.PRNGKey(j) for j in range(4)])
    insts = bbob.stack_instances([bbob.make_instance(1, 8, 1)] * 4)
    carry = jax.eval_shape(jax.vmap(eng.bucketed.full.init_carry), keys)
    sharded = NamedSharding(mesh, PartitionSpec("camp"))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharded),
        (keys, insts, carry))
    runner = eng.ordered_runner(0, eng.bucketed.bucket_seg_gens(0), (1,))
    text = runner.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "all-gather" in text   # cross-chip
