"""The batched round kernels compile for a TPU v5e, without the chip.

Interpret mode (every other kernel test) cannot see what the TPU's compiler
refuses: a block that breaks the (8, 128) tiling rule, an in-kernel gather
Mosaic does not lower, more VMEM than a kernel may take. Each test here
compiles one of the six batched kernels the sweep engine drives (dense and
ELLPACK; plain, receiver-masked, sender-masked) for a described v5e chip at
real widths (G > 1) and checks the program holds the Mosaic kernel.

The topology is described, and every sharding built, inside module-scoped
fixtures: only the process that runs these tests loads the TPU compiler,
and a host where it cannot be described skips them.
"""
import importlib
import os

import pytest

DENSE = dict(g=8, n=256, f=512)
SPARSE = dict(g=8, n=4096, d=8, f=128, e=16384)


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip is written to the cache but cannot
        # be read back without one: keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _kernels():
    return (importlib.import_module("repro.kernels.gossip_round"),
            importlib.import_module("repro.kernels.segment_round"))


def _dense_case(variant, sds):
    gr, _ = _kernels()
    g, n, f = DENSE["g"], DENSE["n"], DENSE["f"]
    w = sds((g, n, n))
    x = sds((g, n, f))
    coef = sds((g, 3))
    kw = dict(bm=128, bk=128, bf=512, interpret=False)
    if variant == "plain":
        return (lambda *a: gr.gossip_round_batched_pallas(*a, **kw),
                (w, x, x, coef))
    fn = (gr.gossip_round_masked_batched_pallas if variant == "masked"
          else gr.gossip_round_sender_masked_batched_pallas)
    return (lambda *a: fn(*a, **kw), (w, sds((g, n, n)), x, x, coef))


def _sparse_case(variant, sds):
    import jax.numpy as jnp

    _, sr = _kernels()
    g, n, d, f, e = (SPARSE[k] for k in ("g", "n", "d", "f", "e"))
    nbr = sds((g, d, n), jnp.int32)
    col = sds((g, d, n))
    diag = sds((g, 1, n))
    bits = sds((g, e))
    x = sds((g, n, f))
    coef = sds((g, 3))
    kw = dict(bm=128, bd=8, bf=128, interpret=False)
    if variant == "plain":
        return (lambda *a: sr.segment_round_batched_pallas(*a, **kw),
                (nbr, col, diag, x, x, coef))
    if variant == "masked":
        return (lambda *a: sr.segment_round_masked_batched_pallas(*a, **kw),
                (nbr, col, nbr, diag, bits, x, x, coef))
    return (lambda *a: sr.segment_round_sender_masked_batched_pallas(*a, **kw),
            (nbr, col, col, nbr, diag, bits, x, x, coef))


@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("variant", ["plain", "masked", "sender"])
def test_batched_round_kernel_compiles_for_v5e(one_chip, layout, variant):
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    case = _dense_case if layout == "dense" else _sparse_case
    fn, args = case(variant, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # operands, result and temporaries of one round fit one 16 GB chip
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, total
