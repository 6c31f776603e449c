"""Ahead-of-time compiles of the device path for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, so what Mosaic or XLA would refuse on the chip
(tiling, unsupported primitives, scoped VMEM overflow) fails here. Nothing
runs, so these tests say nothing about results or times.

The kernels' ``ops`` wrappers pick Mosaic or the Pallas interpreter from
``on_tpu()``, which sees this process's CPU backend; the fixture steers it
to ``True`` so they emit Mosaic. It also clears JAX's trace caches on the
way in and out, so no interpret-mode trace leaks into these compiles and
no Mosaic trace leaks into later CPU tests. All the compiles stay in this
one file: the topology's library is loaded once per process.
"""

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

V5E_HBM_BYTES = 16 * 10**9  # one TPU v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler or topology here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.auction_bid.ops as bid_ops
    import repro.kernels.auction_fused.ops as fused_ops

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fused_ops, "on_tpu", lambda: True)
        mp.setattr(bid_ops, "on_tpu", lambda: True)
        jax.clear_caches()
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


@pytest.mark.parametrize("n", [128, 256, 1024])
def test_auction_bid_compiles_for_tpu(one_chip, n):
    from repro.kernels.auction_bid.ops import masked_row_top2

    _compile(
        masked_row_top2, _spec((n, n), one_chip), _spec((n,), one_chip)
    )


@pytest.mark.parametrize("n,B", [(256, 0), (1024, 0), (1024, 2)])
def test_auction_fused_compiles_for_tpu(one_chip, n, B):
    from repro.core.jaxopt.matching import default_max_iters, default_num_phases
    from repro.kernels.auction_fused import fused_auction

    def run(W, p0, eps):
        # with_iters: the rounds of each phase come out of an SMEM block.
        return fused_auction(
            W, p0, eps, max_iters=default_max_iters(n), use_kernel=True,
            with_iters=True,
        )

    lead = (B,) if B else ()
    fn = jax.vmap(run) if B else run
    compiled = _compile(
        fn,
        _spec(lead + (n, n), one_chip),
        _spec(lead + (n,), one_chip),
        _spec(lead + (default_num_phases(n),), one_chip),
    )
    iters = compiled.out_info[3]
    assert iters.shape == lead + (default_num_phases(n),)
    assert iters.dtype == jnp.int32
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES


@pytest.mark.parametrize(
    "B,n,matcher", [(4, 256, "auction_fused"), (4, 100, "auction_fr")]
)
def test_spectra_e2e_many_compiles_for_tpu(one_chip, B, n, matcher):
    from repro.core.jaxopt.e2e import spectra_jax_e2e_many
    from repro.core.jaxopt.matching import default_matcher

    assert default_matcher(n) == matcher  # the autotuned bucket at this n

    def run(Ds, deltas):
        return spectra_jax_e2e_many(
            Ds, 4, deltas, use_kernel=True, matcher=matcher
        )

    _compile(run, _spec((B, n, n), one_chip), _spec((B,), one_chip))


def test_online_scan_compiles_for_tpu(one_chip):
    from repro.core.jaxopt.matching import default_matcher
    from repro.core.jaxopt.online_jax import spectra_online_scan

    T, n = 8, 64

    def run(Ds, deltas):
        return spectra_online_scan(
            Ds, 4, deltas, use_kernel=True, matcher=default_matcher(n)
        )

    _compile(run, _spec((T, n, n), one_chip), _spec((T,), one_chip))
