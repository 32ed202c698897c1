"""Tests for the async actor/learner runtime (repro.runtime).

Pinned properties:
  * async at staleness bound 0 with the full cohort reproduces the
    synchronous FederatedAveraging loop BITWISE (shared codec);
  * transports carry integer payloads exactly (thread and process);
  * the round buffer rejects stale / unknown / desynchronized updates
    and accepts within the bound;
  * retry/backoff survives injected transport loss;
  * wall-clock stragglers land stale: rejected at bound 0, used (and
    down-weighted) at bound >= 1.
"""
import dataclasses
import queue
import threading

import numpy as np
import pytest

from repro.fl.federated import FLConfig, FederatedAveraging
from repro.runtime import (
    AsyncFederatedRuntime,
    ClientSpec,
    ClientUpdate,
    QuadraticWorkload,
    RoundAnnounce,
    RoundBuffer,
    RoundProtocol,
    RuntimeConfig,
    SHUTDOWN,
    TransportError,
    make_transport,
    protocol,
    run_client,
)
from repro.runtime.actors import staleness_weight
from repro.runtime.transport import ClientEndpoint

N, D, SEED = 6, 48, 3


def _fl(mechanism="aggregate_gaussian", **kw):
    base = dict(n_clients=N, mechanism=mechanism, sigma=1e-3, clip=2.0,
                cohort_fraction=0.8, straggler_fraction=0.2, lr=0.3,
                seed=SEED)
    base.update(kw)
    return FLConfig(**base)


def _warm_codec(proto: RoundProtocol, n: int, d: int) -> None:
    """Compile encode/decode outside the timed round loop so short round
    timeouts in the tests measure runtime behaviour, not jit."""
    key = protocol.round_key(SEED, 0)
    msgs = np.stack([proto.client_message(key, n, p, np.zeros(d, np.float32))
                     for p in range(n)])
    proto.decode(key, n, msgs, np.ones(n, bool))


# ------------------------------------------------- sync/async equivalence
@pytest.mark.parametrize("mechanism", ["aggregate_gaussian",
                                       "individual_shifted"])
def test_async_staleness0_matches_sync_bitwise(mechanism):
    fl = _fl(mechanism)
    wl = QuadraticWorkload(N, D, seed=SEED)
    grad = wl.build()

    fa = FederatedAveraging(fl, lambda p, c, r: grad(np.asarray(p), c, r))
    p_sync = wl.init_params()
    for rnd in range(4):
        p_sync, m = fa.round(p_sync, rnd)
    assert 0 < m["bits_per_coord"] < 32

    rt = AsyncFederatedRuntime(
        RuntimeConfig(fl=fl, staleness_bound=0, quorum=1.0,
                      round_timeout_s=30.0), wl)
    p_async, summary, _ = rt.run(wl.init_params(), 4)
    assert summary["rounds"] == 4
    assert summary["mean_cohort_occupancy"] == 1.0
    np.testing.assert_array_equal(np.asarray(p_sync), p_async)


def test_protocol_straggler_renormalization():
    """Decoding a strict subset renormalizes by the realized count: the
    result tracks the subset mean (announced-n step, realized-r divisor)."""
    proto = RoundProtocol(mechanism="aggregate_gaussian", sigma=1e-3,
                          clip=2.0)
    key = protocol.round_key(0, 0)
    rng = np.random.default_rng(0)
    xs = rng.uniform(-1, 1, (N, D)).astype(np.float32)
    msgs = np.stack([proto.client_message(key, N, p, xs[p])
                     for p in range(N)])
    mask = np.array([True, True, False, True, False, True])
    y, bits = proto.decode(key, N, msgs, mask)
    err = np.asarray(y) - xs[mask].mean(0)
    assert np.abs(err).max() < 20 * proto.sigma, np.abs(err).max()
    assert 0 < bits < 32


# ------------------------------------------------------------- transport
@pytest.mark.parametrize("kind", ["thread", "process"])
def test_transport_integer_roundtrip_exact(kind):
    """A real client actor behind each transport produces byte-identical
    integer payloads to a local encode with the same protocol."""
    fl = _fl(n_clients=2, cohort_fraction=1.0, straggler_fraction=0.0)
    proto = RoundProtocol(mechanism=fl.mechanism, sigma=fl.sigma,
                          clip=fl.clip)
    wl = QuadraticWorkload(2, D, seed=SEED)
    transport = make_transport(kind, 2)
    specs = [ClientSpec(client_id=i, seed=fl.seed, proto=proto, workload=wl)
             for i in range(2)]
    transport.start_clients(run_client, specs)
    ep = transport.learner_endpoint()
    try:
        params = wl.init_params()
        ep.broadcast(RoundAnnounce(rnd=0, cohort=(0, 1), params=params))
        got = {}
        for _ in range(400):
            upd = ep.poll(timeout=0.25)
            if upd is not None:
                got[upd.cohort_pos] = upd
            if len(got) == 2:
                break
        assert len(got) == 2
        grad = wl.build()
        key = protocol.round_key(fl.seed, 0)
        for pos in (0, 1):
            expected = proto.client_message(key, 2, pos,
                                            grad(params, pos, 0))
            payload = np.asarray(got[pos].payload)
            assert payload.dtype == expected.dtype
            np.testing.assert_array_equal(payload, expected)
            np.testing.assert_array_equal(
                np.asarray(got[pos].dither_seed),
                np.asarray(protocol.client_dither_key(key, 2, pos)))
    finally:
        ep.broadcast(SHUTDOWN)
        transport.shutdown()


def test_process_transport_refuses_accelerator_parent(monkeypatch):
    """A chip belongs to the process that touched it first: with a
    non-CPU backend in the parent, spawning clients fails loudly
    instead of leaving children that cannot reach the device."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    transport = make_transport("process", 1)
    spec = ClientSpec(client_id=0, seed=SEED,
                      proto=RoundProtocol(mechanism="aggregate_gaussian",
                                          sigma=1e-2, clip=1.0),
                      workload=QuadraticWorkload(1, D, seed=SEED))
    try:
        with pytest.raises(RuntimeError, match="needs a CPU backend"):
            transport.start_clients(run_client, [spec])
        assert transport._procs == []
    finally:
        transport.shutdown()


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    without it the cache sits at the checkout's fixed .jax_cache."""
    import pathlib

    import jax

    from repro import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_persistent_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    default = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
    assert compile_cache.use_persistent_cache() == str(default)
    assert updates == [("jax_compilation_cache_dir", str(default))]


def test_client_endpoint_drop_injection_and_retry():
    """Injected loss raises TransportError; the actor's bounded retry
    eventually lands every update (deterministic drop rng)."""
    down, up = queue.Queue(), queue.Queue()
    ep = ClientEndpoint(0, down, up, drop_prob=0.9, drop_seed=1)
    upd = ClientUpdate(client_id=0, origin_round=0, cohort_pos=0,
                       payload=np.arange(4, dtype=np.int32),
                       dither_seed=np.zeros(2, np.uint32))
    raised = 0
    for attempt in range(50):
        try:
            ep.send(dataclasses.replace(upd, attempt=attempt))
            break
        except TransportError:
            raised += 1
    assert raised > 0 and up.qsize() == 1


def test_runtime_survives_lossy_transport():
    fl = _fl(cohort_fraction=1.0, straggler_fraction=0.0)
    wl = QuadraticWorkload(N, D, seed=SEED)
    rt = AsyncFederatedRuntime(
        RuntimeConfig(fl=fl, quorum=1.0, round_timeout_s=30.0,
                      drop_prob=0.4, max_retries=8, retry_backoff_s=0.001),
        wl)
    _, summary, _ = rt.run(wl.init_params(), 3)
    assert summary["rounds"] == 3
    assert summary["empty_rounds"] == 0
    assert summary["mean_cohort_occupancy"] == 1.0


# ---------------------------------------------------------- round buffer
def _upd(rnd, pos, cid=None, seed=None):
    return ClientUpdate(client_id=cid if cid is not None else pos,
                        origin_round=rnd, cohort_pos=pos,
                        payload=np.ones(3, np.int32),
                        dither_seed=seed if seed is not None
                        else np.asarray([rnd, pos], np.uint32))


def _register(buf, rnd, cohort):
    seeds = np.stack([np.asarray([rnd, p], np.uint32)
                      for p in range(len(cohort))])
    buf.register_round(rnd, cohort, seeds)


def test_buffer_staleness_and_validation():
    buf = RoundBuffer(staleness_bound=1)
    _register(buf, 0, (0, 1, 2))
    _register(buf, 1, (0, 2))
    _register(buf, 2, (1, 2))

    assert buf.offer(_upd(2, 0, cid=1), server_round=2) == "accepted"
    assert buf.offer(_upd(1, 1, cid=2), server_round=2) == "accepted"  # s=1
    assert buf.offer(_upd(0, 0), server_round=2) == "stale"            # s=2
    assert buf.offer(_upd(5, 0), server_round=2) == "unknown_round"
    # wrong client at the claimed position
    assert buf.offer(_upd(2, 0, cid=0), server_round=2) == "bad_seed"
    # right client, wrong dither seed (desynchronized)
    assert buf.offer(_upd(2, 1, cid=2, seed=np.asarray([9, 9], np.uint32)),
                     server_round=2) == "bad_seed"
    assert buf.offer(_upd(2, 0, cid=1), server_round=2) == "duplicate"

    groups = buf.drain(server_round=2)
    assert sorted(groups) == [1, 2]
    assert list(groups[1]) == [1] and list(groups[2]) == [0]
    assert buf.size == 0
    # round 0 fell out of the window during drain -> now unknown
    assert buf.offer(_upd(0, 0), server_round=2) == "unknown_round"
    assert buf.stats.rejected_stale == 1
    assert buf.stats.duplicates == 1


def test_buffer_capacity_evicts_oldest_first():
    buf = RoundBuffer(staleness_bound=4, capacity=3)
    _register(buf, 0, (0, 1, 2))
    _register(buf, 1, (0, 1, 2))
    for rnd in (0, 1):
        for pos in range(2):
            buf.offer(_upd(rnd, pos), server_round=1)
    assert buf.size == 3 and buf.stats.evicted == 1
    assert buf.count(1) == 2  # newest round untouched
    assert buf.count(0) == 1


def test_staleness_weighting_modes():
    assert staleness_weight(0, "uniform") == 1.0
    assert staleness_weight(3, "uniform") == 1.0
    assert staleness_weight(0, "inverse") == 1.0
    assert staleness_weight(3, "inverse") == pytest.approx(0.25)
    with pytest.raises(KeyError):
        staleness_weight(1, "exponential")


# --------------------------------------------------- stragglers end-to-end
def _straggler_summary(staleness_bound):
    fl = _fl(cohort_fraction=1.0, straggler_fraction=0.0, n_clients=4)
    wl = QuadraticWorkload(4, D, seed=SEED)
    rt = AsyncFederatedRuntime(
        RuntimeConfig(fl=fl, staleness_bound=staleness_bound,
                      staleness_weighting="inverse", quorum=0.5,
                      round_timeout_s=0.25, straggler_fraction=0.5,
                      straggler_delay_s=0.5),
        wl)
    _warm_codec(rt.proto, 4, D)
    _, summary, _ = rt.run(wl.init_params(), 8)
    return summary


def test_wallclock_stragglers_rejected_at_bound0_used_at_bound2():
    s0 = _straggler_summary(0)
    assert s0["rounds"] == 8
    assert s0["stale_updates_used"] == 0
    assert s0["rejected_stale"] > 0  # late arrivals refused

    s2 = _straggler_summary(2)
    assert s2["rounds"] == 8
    assert s2["stale_updates_used"] > 0  # late arrivals recovered
    hist = {int(k): v for k, v in s2["staleness_hist"].items()}
    assert max(hist) <= 2  # never beyond the bound
