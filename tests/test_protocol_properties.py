"""Property-based tests (hypothesis) for Gleam's reliability invariants.

The two §3.4 principles, as executable properties over arbitrary feedback
interleavings and loss patterns:

  (i)  an aggregated ACK for PSN p is emitted only when EVERY downstream
       port has acknowledged p (aggregate == min over ports);
  (ii) a NACK with expected PSN e is forwarded only when every port has
       acknowledged every PSN < e, and the minimum outstanding loss is
       never masked (Fig. 7).

Plus end-to-end: under any random loss pattern the multicast still
delivers every byte to every receiver (go-back-N + aggregation compose).
"""
from __future__ import annotations

import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import HealthCheck, given, settings, strategies as st  # noqa: E402

from repro.core import fattree, packet as pk
from repro.core.gleam import GleamNetwork
from repro.core.switch import GleamSwitch

FAST = dict(deadline=None,
            suppress_health_check=[HealthCheck.too_slow,
                                   HealthCheck.data_too_large])


def fresh_switch(n_receivers: int):
    topo = fattree.testbed(n_hosts=n_receivers + 1)
    hosts = fattree.host_ip_map(topo)
    sw = GleamSwitch("SW0", topo, hosts)
    t = sw.tables.create(group_ip=4242)
    for port in range(n_receivers + 1):
        t.add_connected(port, dest_ip=port + 1, dest_qpn=16 + port)
    t.ack_out_port = 0              # port 0 faces the source
    return sw, t


feedback_event = st.tuples(
    st.integers(min_value=1, max_value=4),      # receiver port
    st.sampled_from(["ack", "nack"]),
    st.integers(min_value=0, max_value=63),     # psn
)


@settings(max_examples=200, **FAST)
@given(st.lists(feedback_event, min_size=1, max_size=120))
def test_aggregated_ack_is_min_over_ports(events):
    sw, t = fresh_switch(4)
    acked = {p: -1 for p in range(1, 5)}        # per-port cumulative
    for port, kind, psn in events:
        if kind == "ack":
            pkt = pk.ack_packet(src_ip=port + 1, dst_ip=4242, psn=psn)
        else:
            pkt = pk.nack_packet(src_ip=port + 1, dst_ip=4242, epsn=psn)
        out = sw.on_packet(pkt, port, 0.0)
        if kind == "ack":
            acked[port] = max(acked[port], psn)
        else:
            acked[port] = max(acked[port], psn - 1)
        floor = min(acked.values())
        for _, p in out:
            if p.kind == pk.ACK:
                # (i): never ack beyond the slowest receiver
                assert p.psn <= floor, (
                    f"aggregated ACK {p.psn} > min acked {floor}")


@settings(max_examples=200, **FAST)
@given(st.lists(feedback_event, min_size=1, max_size=120))
def test_nack_never_masks_earlier_loss(events):
    """(ii): any NACK forwarded upstream must carry the MINIMUM expected
    PSN outstanding at that moment — forwarding a higher one would mask
    the earlier loss (Fig. 7)."""
    sw, t = fresh_switch(4)
    acked = {p: -1 for p in range(1, 5)}
    for port, kind, psn in events:
        if kind == "ack":
            pkt = pk.ack_packet(src_ip=port + 1, dst_ip=4242, psn=psn)
            out = sw.on_packet(pkt, port, 0.0)
            acked[port] = max(acked[port], psn)
        else:
            pkt = pk.nack_packet(src_ip=port + 1, dst_ip=4242, epsn=psn)
            out = sw.on_packet(pkt, port, 0.0)
            acked[port] = max(acked[port], psn - 1)
        floor = min(acked.values())
        for _, p in out:
            if p.kind == pk.NACK:
                assert p.psn == floor + 1, (
                    f"NACK {p.psn} != min outstanding {floor + 1}")


@settings(max_examples=150, **FAST)
@given(st.lists(st.integers(min_value=0, max_value=63),
                min_size=1, max_size=100),
       st.integers(min_value=2, max_value=4))
def test_ack_stream_monotonic(psns, n_recv):
    """The sender-facing aggregated ACK stream is strictly increasing —
    the 'unicast-like feedback stream' RC logic requires."""
    sw, t = fresh_switch(n_recv)
    seen = []
    for i, psn in enumerate(psns):
        port = (i % n_recv) + 1
        out = sw.on_packet(pk.ack_packet(port + 1, 4242, psn), port, 0.0)
        seen += [p.psn for _, p in out if p.kind == pk.ACK]
    assert seen == sorted(set(seen)), f"non-monotonic ACK stream {seen}"


@settings(max_examples=12, **FAST)
@given(loss=st.floats(min_value=0.0, max_value=5e-3),
       seed=st.integers(min_value=0, max_value=2 ** 16),
       nbytes=st.integers(min_value=1, max_value=1 << 19))
def test_end_to_end_reliable_delivery_under_loss(loss, seed, nbytes):
    """Whatever the loss pattern, every receiver eventually gets every
    byte and the sender gets exactly one CQE (hardware reliability)."""
    net = GleamNetwork(fattree.testbed(), loss_rate=loss, seed=seed)
    g = net.multicast_group(["h0", "h1", "h2", "h3"])
    g.register()
    rec = g.bcast(nbytes)
    jct = g.run_until_delivered(rec, timeout=30.0)
    assert jct < float("inf"), "multicast did not complete"
    for h in ("h1", "h2", "h3"):
        assert g.qps[h].delivered_bytes >= nbytes
    assert rec.t_sender_cqe >= max(rec.t_deliver.values()) - 1e-9


@settings(max_examples=30, **FAST)
@given(st.integers(min_value=2, max_value=16))
def test_registration_any_group_size(n):
    topo = fattree.testbed(n_hosts=max(n, 2))
    net = GleamNetwork(topo)
    g = net.multicast_group([f"h{i}" for i in range(n)])
    g.register()
    assert g.registered


churn_event = st.one_of(
    st.tuples(st.just("ack"), st.integers(min_value=1, max_value=6),
              st.integers(min_value=0, max_value=300)),
    st.tuples(st.just("add"), st.integers(min_value=1, max_value=6),
              st.just(0)),
    st.tuples(st.just("remove"), st.integers(min_value=1, max_value=6),
              st.just(0)),
)


@settings(max_examples=200, **FAST)
@given(base=st.integers(min_value=0, max_value=pk.PSN_MOD - 1),
       events=st.lists(churn_event, min_size=1, max_size=80))
def test_agg_min_tracks_bruteforce_under_churn_across_wrap(base, events):
    """The cached aggregate minimum (``GroupTable.agg_min``) must equal
    the brute-force windowed ``psn_min`` fold over the live ports at
    every step — including mid-stream port installs (seeded from
    ``last_ack_psn``), removals of the port OWNING the minimum, and PSN
    streams that wrap through PSN_MOD (``base`` near the top).  The
    emitted aggregated-ACK stream must advance in wrapped order.
    (Driver shared with the deterministic fuzz in test_membership.)"""
    from _membership_props import run_churn_case
    run_churn_case(base, events)


# ------------- loss/DCQCN model invariants (drivers in _loss_props.py;
# deterministic seeded-fuzz twins in test_loss_model.py)

@settings(max_examples=20, **FAST)
@given(group=st.integers(min_value=2, max_value=8),
       transport=st.sampled_from(("gleam", "multiunicast", "ring")),
       l1=st.floats(min_value=0.0, max_value=2e-2),
       l2=st.floats(min_value=0.0, max_value=2e-2),
       nbytes=st.integers(min_value=1 << 12, max_value=1 << 20))
def test_flow_jct_monotone_nondecreasing_in_loss(group, transport, l1,
                                                 l2, nbytes):
    from _loss_props import run_monotone_case
    run_monotone_case(group, transport, l1, l2, nbytes)


@settings(max_examples=60, **FAST)
@given(seed=st.integers(min_value=0, max_value=2 ** 32 - 1))
def test_loss_factors_bounded_never_exceed_allocation(seed):
    from _loss_props import run_factor_bounds_case
    run_factor_bounds_case(seed)


@settings(max_examples=120, **FAST)
@given(base=st.integers(min_value=0, max_value=pk.PSN_MOD - 1),
       n_pkts=st.integers(min_value=1, max_value=600),
       window=st.sampled_from((4, 32, 256)),
       plan=st.lists(st.tuples(
           st.sampled_from(["ack", "nack", "timeout"]),
           st.integers(min_value=0, max_value=700)),
           min_size=1, max_size=60))
def test_gbn_replay_bounded_by_window_across_wrap(base, n_pkts, window,
                                                  plan):
    from _loss_props import run_gbn_replay_case
    run_gbn_replay_case(base, n_pkts, window, plan)


@settings(max_examples=10, **FAST)
@given(n_hosts=st.integers(min_value=3, max_value=10),
       loss=st.floats(min_value=0.0, max_value=1e-2),
       seed=st.integers(min_value=0, max_value=2 ** 16),
       nbytes=st.integers(min_value=1 << 12, max_value=1 << 17))
def test_e2e_retransmission_bounded_by_drops(n_hosts, loss, seed,
                                             nbytes):
    from _loss_props import run_e2e_retrans_case
    run_e2e_retrans_case(n_hosts, loss, seed, nbytes)


@settings(max_examples=60, **FAST)
@given(a=st.integers(min_value=0, max_value=pk.PSN_MOD - 1),
       d=st.integers(min_value=0, max_value=(1 << 22) - 1))
def test_psn_wrapped_total_order(a, d):
    """psn_geq is a correct order inside one comparison window, across
    wraparound (both 2^23 and the P4 2^22 windows)."""
    for w in (pk.PSN_WINDOW, pk.PSN_WINDOW_P4):
        b = pk.psn_add(a, d % w)
        assert pk.psn_geq(b, a, w)
        if d % w:
            assert pk.psn_gt(b, a, w)
            assert not pk.psn_geq(a, b, w)
        assert pk.psn_min(a, b, w) == a
        assert pk.psn_max(a, b, w) == b


# --------------- fault-plane invariants (drivers in _fault_props.py;
# deterministic twins in test_faults.py)

@settings(max_examples=25, **FAST)
@given(first=st.floats(min_value=1e-6, max_value=2e-3),
       gap=st.one_of(st.none(),
                     st.floats(min_value=0.0, max_value=1e-3)))
def test_reelection_converges_for_any_crash_schedule(first, gap):
    """Any valid master-crash sequence (1-2 crashes on 4 members,
    spaced past the re-election window) ends with exactly one live
    master — the lowest-rank survivor — the stream complete for every
    surviving receiver, dead hosts dark, and no switch left holding an
    MFT entry for a dead host (the dead-source sever cascade unwinds
    the branches the re-rooted tree bypassed)."""
    from _fault_props import MIN_CRASH_GAP, run_reelection_case
    offsets = [first]
    if gap is not None:
        offsets.append(first + MIN_CRASH_GAP + gap)
    run_reelection_case(offsets, nbytes=1 << 16)


@settings(max_examples=25, **FAST)
@given(cap=st.integers(min_value=0, max_value=8),
       sever_at=st.floats(min_value=1e-6, max_value=5e-5))
def test_bounded_retry_is_terminal_for_any_budget(cap, sever_at):
    """For ANY retry budget and sever instant: a permanently severed
    path costs at most ``cap`` unproductive RTO replays (each bounded
    by the outstanding window) before the QP parks in a terminal
    ``retry_exceeded`` error surfaced on the message record — or the
    message had already beaten the sever and completes cleanly.  Never
    a hang, never unbounded retransmission."""
    from _fault_props import run_bounded_retry_case
    run_bounded_retry_case(cap, sever_at, nbytes=1 << 16)


# --------- dynamic-segment solver invariants (drivers in
# _segment_props.py; deterministic twins in test_segments.py)

@settings(max_examples=40, **FAST)
@given(seed=st.integers(min_value=0, max_value=2 ** 16),
       n_flows=st.integers(min_value=1, max_value=32),
       n_links=st.integers(min_value=2, max_value=40))
def test_vectorized_maxmin_bit_identity(seed, n_flows, n_links):
    """CSR-vectorized ``static_maxmin`` reproduces the original
    per-flow-loop progressive filling bit for bit on arbitrary
    duplicate-free problems."""
    from _segment_props import run_solver_identity_case
    run_solver_identity_case(seed, n_flows=n_flows, n_links=n_links)


@settings(max_examples=8, **FAST)
@given(seed=st.integers(min_value=0, max_value=2 ** 16),
       n_ops=st.integers(min_value=1, max_value=4),
       scenarios=st.booleans())
def test_batched_segments_match_per_segment_oracle(seed, n_ops,
                                                   scenarios):
    """For ANY random membership-event timeline, the batched
    dynamic-segment solver reproduces the legacy per-segment
    ``static_maxmin`` closures bit for bit on the numpy backend
    (zero-event ops included — n_ops=1 in isolated scenarios also
    exercises the lone-op mincap short-circuit)."""
    from _segment_props import run_engine_timeline_case
    run_engine_timeline_case(seed, n_ops=n_ops, engine="flow-np",
                             scenarios=scenarios)


@settings(max_examples=8, **FAST)
@given(seed=st.integers(min_value=0, max_value=2 ** 16),
       n_problems=st.integers(min_value=1, max_value=10),
       with_loss=st.booleans())
def test_device_segment_rates_match_numpy_oracle(seed, n_problems,
                                                 with_loss):
    """The device (JAX) batched segment solver matches the numpy
    ``segment_rates_many`` oracle to <= 1e-6 relative, with and
    without per-segment loss/DCQCN factors."""
    from _segment_props import run_segment_rates_parity_case
    run_segment_rates_parity_case(seed, n_problems=n_problems,
                                  with_loss=with_loss)
