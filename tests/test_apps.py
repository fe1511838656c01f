"""Application traffic plane (repro.apps): lowering math, the
open-loop generator, and engine integration.

ISSUE-8 satellite checklist:

- collective sizes match the ArchConfig math for >= 3 archs (dense,
  MoE, hybrid-SSM), anchored on ``count_params(model_defs(cfg))`` —
  the analytic mirror must track the real tensor shapes exactly;
- seeded Poisson arrivals are deterministic (and specs round-trip);
- packet ``run_workloads`` serial == ``workers=N`` bit-identical on app
  workloads;
- packet-vs-flow parity <= 10% on a small phase-split train step.
"""
from __future__ import annotations

import pytest

from repro.apps.collectives_lowering import (BF16, F32, MeshShape,
                                             kv_cache_bytes,
                                             moe_a2a_pair_bytes,
                                             moe_uses_ep, param_count,
                                             pp_boundary_bytes,
                                             tp_allreduce_bytes,
                                             train_step_workload,
                                             weight_bcast_workload)
from repro.apps.metrics import (jct, phase_stats, quantile, run_phased,
                                split_phases, step_time)
from repro.apps.traffic import ArrivalSpec, ServingGenerator
from repro.configs.base import get_config
from repro.core import fattree
from repro.core.engine import make_engine

ARCHS = ("llama3_2_3b",       # dense:      attn + mlp every block
         "mixtral_8x7b",      # MoE:        attn + moe every block
         "jamba_v0_1_52b")    # hybrid-SSM: mamba/attn mix + moe


# ===================================================== lowering math

@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_model_defs(arch):
    """The analytic mirror must equal the real shape table exactly."""
    from repro.models.blocks import count_params
    from repro.models.model import model_defs
    for smoke in (True, False):
        cfg = get_config(arch, smoke=smoke)
        assert param_count(cfg) == count_params(model_defs(cfg))


@pytest.mark.parametrize("arch", ARCHS)
def test_tp_allreduce_bytes_from_pattern(arch):
    """units = mixers + dense FFNs (MoE FFNs only when not in ep
    mode); one (batch, seq, d) bf16 activation per unit, x2 for the
    backward."""
    cfg = get_config(arch, smoke=True)
    seq, batch, tp = 64, 8, 2
    ep = moe_uses_ep(cfg, tp)
    units = 0
    for _, ffn in cfg.pattern:
        units += 1
        if ffn == "mlp" or (ffn == "moe" and not ep):
            units += 1
    expect = units * cfg.n_blocks * batch * seq * cfg.d_model * BF16 * 2
    assert tp_allreduce_bytes(cfg, seq, batch, tp) == expect
    # inference = one pass
    assert tp_allreduce_bytes(cfg, seq, batch, tp, kind="prefill") \
        == expect // 2


def test_moe_a2a_pair_bytes_mixtral():
    """ep mode: per a2a each ordered pair carries tokens/ep * top_k *
    d * 2 / ep bytes; dispatch+combine per MoE sublayer, x2 train."""
    cfg = get_config("mixtral_8x7b", smoke=True)
    seq, batch, ep = 64, 8, 2
    assert moe_uses_ep(cfg, ep)
    n_moe = sum(1 for _, f in cfg.pattern if f == "moe") * cfg.n_blocks
    per = batch * seq * cfg.top_k * cfg.d_model * BF16 // (ep * ep)
    assert moe_a2a_pair_bytes(cfg, seq, batch, ep) == per * n_moe * 2 * 2


def test_kv_cache_bytes_hybrid():
    """Hybrid arch: bf16 K+V per attn sublayer grows with seq; f32 SSD
    state per mamba sublayer does not."""
    cfg = get_config("jamba_v0_1_52b", smoke=True)
    attn = sum(1 for m, _ in cfg.pattern if m == "attn")
    mamba = sum(1 for m, _ in cfg.pattern if m == "mamba")
    assert attn and mamba, "jamba smoke must stay hybrid"
    seq = 128
    d_in = cfg.ssm_expand * cfg.d_model
    h = d_in // cfg.ssm_headdim
    expect = (attn * 2 * seq * cfg.n_kv_heads * cfg.hd * BF16
              + mamba * (h * cfg.ssm_headdim * cfg.ssm_state
                         + (cfg.ssm_conv - 1) * d_in) * F32
              ) * cfg.n_blocks
    assert kv_cache_bytes(cfg, seq) == expect
    # the mamba share is seq-free
    delta = kv_cache_bytes(cfg, 2 * seq) - kv_cache_bytes(cfg, seq)
    assert delta == attn * 2 * seq * cfg.n_kv_heads * cfg.hd * BF16 \
        * cfg.n_blocks


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_workload_structure(arch):
    cfg = get_config(arch, smoke=True)
    # jamba's smoke config is a single block -> no pipeline cut there
    pipe = 2 if cfg.n_blocks % 2 == 0 else 1
    mesh = MeshShape(data=2, model=2, pipe=pipe)
    wl = train_step_workload(cfg, mesh, seq=64, batch=8, accum=2)
    by_phase = {}
    for op in wl.ops:
        by_phase.setdefault(op.phase, []).append(op)
    # one TP all-reduce per (pipe, data) group
    assert len(by_phase["tp-allreduce"]) == mesh.pipe * mesh.data
    if pipe > 1:
        # one pp unicast per (cut, data, model)
        pp = by_phase["pp-boundary"]
        assert len(pp) == (mesh.pipe - 1) * mesh.data * mesh.model
        assert pp[0].nbytes == pp_boundary_bytes(cfg, 64, 8 // 2 // 2) \
            * 2 * 2 // mesh.model
    else:
        assert "pp-boundary" not in by_phase
    # one grad sync per (pipe, model) over the data axis, f32 shard
    gs = by_phase["dp-gradsync"]
    assert len(gs) == mesh.pipe * mesh.model
    assert gs[0].nbytes == F32 * param_count(cfg) \
        // (mesh.model * mesh.pipe)
    if moe_uses_ep(cfg, mesh.model):
        # a full fan-mesh: tp*(tp-1) ordered pairs per TP group
        assert len(by_phase["moe-alltoall"]) == \
            mesh.pipe * mesh.data * mesh.model * (mesh.model - 1)
    else:
        assert "moe-alltoall" not in by_phase
    # phase-split partitions the ops exactly
    parts = split_phases(wl)
    assert sorted(id(o) for p in parts for o in p.ops) \
        == sorted(id(o) for o in wl.ops)
    assert all(p.meta == wl.meta for p in parts)


def test_weight_bcast_is_native_shard():
    cfg = get_config("llama3_2_3b", smoke=True)
    wl = weight_bcast_workload(cfg, 4, 2)
    assert len(wl.ops) == 2                     # one bcast per TP rank
    for m, op in enumerate(wl.ops):
        assert op.op == "bcast" and op.phase == "weights"
        assert op.nbytes == BF16 * param_count(cfg) // 2
        assert list(op.members) == [f"h{r * 2 + m}" for r in range(4)]


def test_train_step_workload_validation():
    cfg = get_config("llama3_2_3b", smoke=True)
    with pytest.raises(ValueError, match="single chip"):
        train_step_workload(cfg, MeshShape(), seq=64, batch=8)
    with pytest.raises(ValueError, match="not divisible"):
        train_step_workload(cfg, MeshShape(data=3), seq=64, batch=8)


# ==================================================== arrivals / specs

def test_poisson_arrivals_deterministic():
    a = ArrivalSpec(rate=1e4, n=32, seed=7)
    xs, ys = a.arrivals(), ArrivalSpec(rate=1e4, n=32, seed=7).arrivals()
    assert xs == ys                              # bit-identical replay
    assert xs == sorted(xs) and len(xs) == 32 and xs[0] > 0
    assert ArrivalSpec(rate=1e4, n=32, seed=8).arrivals() != xs
    # mean gap ~ 1/rate (Mersenne Twister is spec'd, so this is exact
    # across platforms; the loose band just guards the formula)
    assert 0.5 / 1e4 < xs[-1] / 32 < 2.0 / 1e4


def test_arrival_spec_roundtrip_and_validation():
    for spec in (ArrivalSpec(rate=5e3, n=16, seed=3),
                 ArrivalSpec(kind="trace", trace=(3e-4, 1e-4, 2e-4))):
        back = ArrivalSpec.from_dict(spec.to_dict())
        assert back == spec
        assert back.arrivals() == spec.arrivals()
    assert ArrivalSpec(kind="trace", trace=(3e-4, 1e-4)).arrivals() \
        == [1e-4, 3e-4]
    with pytest.raises(ValueError, match="unknown arrival kind"):
        ArrivalSpec(kind="uniform")
    with pytest.raises(ValueError, match="non-empty trace"):
        ArrivalSpec(kind="trace")
    with pytest.raises(ValueError, match="unknown ArrivalSpec fields"):
        ArrivalSpec.from_dict({"kind": "poisson", "burst": 4})


def test_quantiles_nearest_rank():
    xs = list(range(1, 101))                     # 1..100
    assert quantile(xs, 0.50) == 50
    assert quantile(xs, 0.99) == 99
    assert quantile(xs, 0.999) == 100
    assert quantile([], 0.5) == 0.0
    assert quantile([42.0], 0.999) == 42.0


# ================================================= engine integration

def _small_train_wl(transport="gleam"):
    cfg = get_config("llama3_2_3b", smoke=True)
    return train_step_workload(cfg, MeshShape(data=2, model=2),
                               seq=64, batch=8, transport=transport)


def test_step_time_sums_phase_maxima():
    wl = _small_train_wl()
    eng = make_engine("flow", fattree.testbed(n_hosts=4))
    ops, recs = run_phased(eng, wl)
    stats = phase_stats(ops, recs)
    assert set(stats) == {"tp-allreduce", "dp-gradsync"}
    assert step_time(ops, recs) == pytest.approx(
        sum(s.latency for s in stats.values()))
    # an overlappable compute floor clips a cheaper phase
    big = {"tp-allreduce": 10.0}
    assert step_time(ops, recs, big) == pytest.approx(
        10.0 + stats["dp-gradsync"].latency)


@pytest.mark.parametrize("transport", ["gleam", "multiunicast"])
def test_train_step_packet_flow_parity(transport):
    """Phase-split step time: the two engines must agree within 10%."""
    wl = _small_train_wl(transport)
    out = {}
    for name in ("packet", "flow"):
        eng = make_engine(name, fattree.testbed(n_hosts=4))
        ops, recs = run_phased(eng, wl, timeout=120.0)
        out[name] = step_time(ops, recs)
    div = abs(out["packet"] - out["flow"]) / out["packet"]
    assert div <= 0.10, f"{transport}: packet={out['packet']:.3e} " \
                        f"flow={out['flow']:.3e} div={div:.1%}"


def test_packet_serial_matches_workers():
    """App batches ride packet run_workloads: forked workers must be
    bit-identical to the serial fallback."""
    wl = _small_train_wl()
    phases = split_phases(wl)
    runs = []
    for workers in (1, 2):
        eng = make_engine("packet", fattree.testbed(n_hosts=4))
        res = eng.run_workloads(phases, timeout=120.0, workers=workers)
        runs.append([sorted(r.t_deliver.values()) for rs in res
                     for r in rs])
    assert runs[0] == runs[1]


def test_serving_generator_end_to_end():
    cfg = get_config("llama3_2_3b", smoke=True)
    gen = ServingGenerator(cfg, n_replicas=4, tp=2, prompt_len=32,
                           decode_len=8, kv_replicas=2)
    spec = ArrivalSpec(rate=2e4, n=16, seed=0)
    wls = gen.workloads(spec)
    assert sum(len(wl.meta["requests"]) for wl in wls) == 16
    # per request: prefill + decode all-reduce + kv write
    assert sum(len(wl.ops) for wl in wls) == 3 * 16
    kv = [op for wl in wls for op in wl.ops if op.phase == "kv-replicate"]
    assert all(op.op == "write" and len(op.members) == 3 for op in kv)
    eng = make_engine("flow", fattree.testbed(n_hosts=8))
    rep = gen.run(eng, spec)
    assert rep.n_requests == 16
    assert 0 < rep.achieved_qps <= spec.rate * 1.5
    assert rep.quantiles["p50"] <= rep.quantiles["p99"] \
        <= rep.quantiles["p999"] <= rep.quantiles["max"]
    assert len(rep.latencies) == 16 and min(rep.latencies) > 0
    assert set(rep.phase_latency) == {"prefill", "decode",
                                      "kv-replicate"}
    # same spec, same engine family => same report (replayable)
    rep2 = gen.run(make_engine("flow", fattree.testbed(n_hosts=8)), spec)
    assert rep2.latencies == rep.latencies


def test_serving_generator_validation():
    cfg = get_config("llama3_2_3b", smoke=True)
    with pytest.raises(ValueError, match=">= 2 replicas"):
        ServingGenerator(cfg, n_replicas=1, tp=2)
    with pytest.raises(ValueError, match="kv_replicas"):
        ServingGenerator(cfg, n_replicas=2, tp=2, kv_replicas=2)


def test_workload_meta_and_phase_roundtrip():
    """The app plane's IR additions survive the dict round-trip."""
    from repro.core.workload import Workload
    gen = ServingGenerator(get_config("llama3_2_3b", smoke=True),
                           n_replicas=2, tp=2)
    wl = gen.workloads(ArrivalSpec(rate=1e4, n=4, seed=1))[0]
    back = Workload.from_dict(wl.to_dict())
    assert back.ops == wl.ops
    assert [op.phase for op in back.ops] == [op.phase for op in wl.ops]
    assert back.meta == wl.meta
    assert ArrivalSpec.from_dict(back.meta["spec"]).arrivals() \
        == ArrivalSpec(rate=1e4, n=4, seed=1).arrivals()


def test_jct_falls_back_to_sender_cqe():
    from repro.core.metrics import MsgRecord
    r = MsgRecord(msg_id=0, nbytes=1, t_submit=1.0, t_sender_cqe=3.5)
    assert jct(r) == 2.5
