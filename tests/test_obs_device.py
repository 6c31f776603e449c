"""Stage scopes, bidding-round counters and the timed collect of the
served device program, on the CPU (Pallas kernels in interpret mode)."""

import glob
import time
from functools import lru_cache
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.api import SolveOptions  # noqa: E402
from repro.api.jax_backend import dispatch_many_jax, program_text  # noqa: E402
from repro.core.jaxopt.e2e import spectra_jax_e2e_many  # noqa: E402
from repro.core.jaxopt.matching import (  # noqa: E402
    _eps_schedule,
    default_max_iters,
    default_num_phases,
)
from repro.kernels.auction_fused import fused_auction  # noqa: E402
from repro.obs.device import SCOPES, op_scopes  # noqa: E402
from repro.obs.trace import Tracer  # noqa: E402
from repro.serve.server import ScheduleServer  # noqa: E402

MATCHERS = ("auction", "auction_fr", "auction_fused")
N = 16
# Outputs of the fused program on ``_lanes()`` before the counters and
# scopes went in; they change no schedule, so these stay bit-identical.
EXPECTED = Path(__file__).parent / "data" / "e2e_matchers_n16.npz"


def _lanes():
    """A single weighted permutation, and a dense matrix."""
    rng = np.random.default_rng(20261018)
    perm = np.zeros((N, N), np.float32)
    perm[np.arange(N), rng.permutation(N)] = 0.5
    dense = rng.random((N, N)).astype(np.float32)
    return perm, dense


@lru_cache(maxsize=None)
def _solve(matcher: str, lanes: str):
    """The fused program with the kernels on (interpreted) over a batch of
    ``lanes``: ``p`` the permutation, ``d`` the dense matrix."""
    perm, dense = _lanes()
    Ds = np.stack([{"p": perm, "d": dense}[c] for c in lanes])
    return spectra_jax_e2e_many(
        Ds, 4, np.float32(0.01), use_kernel=True, matcher=matcher
    )


def _useful_share(dec) -> float:
    """Σ lanes' rounds over B × the rounds the batch ran."""
    rounds = np.asarray(dec.bid_rounds)
    return rounds.sum() / (rounds.size * int(np.asarray(dec.bid_rounds_run).max()))


# ------------------------------------------------------------ stage scopes


def test_op_scopes_takes_innermost_scope():
    text = "\n".join([
        '  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(f)/vmap(spectra.decompose.match)/while/body/'
        'jit(match_auction)/auction.forward_round/max" source_line=1}',
        '  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name='
        '"jit(f)/vmap(spectra.lb)/add"}',
        '  %copy.2 = f32[2]{0} copy(%x), metadata={op_name="jit(f)/vmap()/copy"}',
        '  %neg.1 = f32[2]{0} negate(%x), metadata={op_name='
        '"jit(f)/spectra.lbx/neg"}',
    ])
    assert op_scopes(text) == {
        "fusion.3": "auction.forward_round",
        "add.1": "spectra.lb",
    }


@pytest.mark.parametrize(
    "matcher, round_scopes",
    [("auction", {"auction.forward_round"}), ("auction_fr", set())],
)
def test_program_text_scopes_every_stage(matcher, round_scopes):
    """``auction``'s rounds carry their own scope; ``auction_fr``'s single
    round per iteration falls under ``spectra.decompose.match``."""
    text = program_text(2, N, 4, SolveOptions(extra={"matcher": matcher}))
    found = set(op_scopes(text).values())
    assert {
        "spectra.decompose.match", "spectra.decompose.peel",
        "spectra.refine", "spectra.lpt", "spectra.equalize", "spectra.lb",
    } | round_scopes <= found
    assert found & {"auction.forward_round"} == round_scopes
    assert "auction.reverse_round" not in text
    assert found <= set(SCOPES)


def test_online_step_scopes_its_own_stages():
    from repro.core.jaxopt.online_jax import online_initial_state, online_step_jax

    n, s = 8, 2
    text = online_step_jax.lower(
        online_initial_state(n, s), jnp.ones((n, n), jnp.float32), s,
        jnp.float32(0.01), matcher="auction_fr",
    ).compile().as_text()
    found = set(op_scopes(text).values())
    assert {
        "online.warm_refine", "online.reuse_lpt", "online.credit",
        "spectra.decompose.match", "spectra.lpt", "spectra.equalize",
        "spectra.lb",
    } <= found


# --------------------------------------------------- bidding-round counters


@pytest.mark.parametrize("matcher", MATCHERS)
def test_single_lane_runs_its_own_rounds(matcher):
    dec = _solve(matcher, "d").dec
    assert int(dec.bid_rounds[0]) > 0
    assert int(dec.bid_rounds_run[0]) == int(dec.bid_rounds[0])


@pytest.mark.parametrize("matcher", MATCHERS)
def test_identical_lanes_read_full_useful_share(matcher):
    dec = _solve(matcher, "dd").dec
    assert int(dec.bid_rounds[0]) == int(dec.bid_rounds[1]) > 0
    assert _useful_share(dec) == 1.0


@pytest.mark.parametrize("matcher", MATCHERS)
def test_uneven_lanes_waste_rounds(matcher):
    """The permutation needs one matching, the dense matrix many: the batch
    runs the dense lane's rounds (and, in lockstep, the finished lane's)."""
    dec = _solve(matcher, "pd").dec
    rounds = np.asarray(dec.bid_rounds)
    run = np.asarray(dec.bid_rounds_run)
    assert (rounds > 0).all()
    assert (run >= rounds).all() and run.max() >= rounds.max()
    assert _useful_share(dec) < 1.0


@pytest.mark.parametrize("matcher", MATCHERS)
def test_counters_change_no_schedule(matcher):
    res = _solve(matcher, "pd")
    expected = np.load(EXPECTED)
    got = {
        "dec_perms": res.dec.perms, "dec_alphas": res.dec.alphas,
        "dec_k": res.dec.k, "perms": res.schedule.perms,
        "alphas": res.schedule.alphas, "switch": res.schedule.switch,
        "makespan": res.makespan,
    }
    for key, value in got.items():
        np.testing.assert_array_equal(
            np.asarray(value), expected[f"{matcher}.{key}"], err_msg=key
        )


@pytest.mark.parametrize("n", [5, 37])
def test_fused_kernel_counts_equal_reference(n):
    W = jnp.asarray(np.random.default_rng(n).random((n, n)), jnp.float32)
    p0 = jnp.zeros((n,), jnp.float32)
    eps = _eps_schedule(W, default_num_phases(n))
    kw = dict(max_iters=default_max_iters(n), with_iters=True)
    ker = fused_auction(W, p0, eps, use_kernel=True, interpret=True, **kw)
    ref = fused_auction(W, p0, eps, use_kernel=False, **kw)
    assert ker[3].shape == (default_num_phases(n),)
    assert int(ker[3].min()) > 0
    np.testing.assert_array_equal(np.asarray(ker[3]), np.asarray(ref[3]))


# ------------------------------------------------------------ timed collect


def test_collect_splits_wait_from_host_work():
    Ds = np.stack(_lanes()).astype(np.float64)
    options = SolveOptions(validate=True)
    pending = dispatch_many_jax(Ds, 4, 0.01, options)
    t0 = time.perf_counter()
    reports = pending.collect()
    total = time.perf_counter() - t0
    wait, host = (pending.timings["collect_wait_s"],
                  pending.timings["collect_host_s"])
    assert wait >= 0 and host > 0 and wait + host <= total
    assert [r.extras["bid_rounds"] > 0 for r in reports] == [True, True]
    assert pending.collect() is reports and pending.timings["collect_host_s"] == host


def test_served_results_carry_collect_time_and_flight():
    server = ScheduleServer(4, 0.01, max_batch=2,
                            options=SolveOptions(validate=True))
    perm, dense = _lanes()
    for i, D in enumerate((perm, dense, dense, perm)):
        server.submit(f"tenant{i}", D)
    server.drain()
    results = [server.results[t] for t in range(4)]
    assert [r.flight for r in results] == [0, 0, 1, 1]
    for r in results:
        assert r.timings["collect_host_s"] > 0
        assert r.timings["collect_wait_s"] >= 0
        assert r.report.extras["bid_rounds_run"] >= r.report.extras["bid_rounds"]


# ------------------------------------------------------ one clock with JAX


def test_enabled_span_is_a_profiler_host_event(tmp_path):
    from jax.profiler import ProfileData

    on, off = Tracer(enabled=True), Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with on.span("serve.dispatch", {"flight": 7}):
            with off.span("serve.not_recorded"):
                jnp.ones(4).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert "serve.dispatch" in names
    assert "serve.not_recorded" not in names
    assert [e.name for e in on.spans()] == ["serve.dispatch"]
