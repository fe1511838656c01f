"""DeepSeek-V3 through the traffic plane (``configs/deepseek_v3.py``,
``apps/collectives_lowering.py``'s expert axis).

- the config holds config.json's numbers, and ``param_count`` equals a
  count written out here from those numbers, tensor by tensor;
- the node-limited router equals a plain per-token loop of the
  published rule;
- dispatch and combine sizes equal a hand-derived table, no op stays
  inside a node, and the full step at ``MeshShape(pipe=16, data=128,
  expert=64)`` on a 2,048-host rail placement has its pinned sizes;
- meshes without an expert axis lower byte for byte as before;
- packet and flow engines agree on one SMOKE EP group, and the gleam
  dispatch sends fewer bytes out of every source NIC.
"""
from __future__ import annotations

import hashlib
import json
from collections import Counter

import numpy as np
import pytest

from repro.apps.collectives_lowering import (
    F32, MeshShape, combine_token_bytes, dispatch_token_bytes,
    kv_cache_bytes, moe_ep_ops, node_limited_sets, node_token_counts,
    param_count, rail_hosts, route_ep_groups, stage_layers,
    train_step_workload, weight_bcast_workload, tp_allreduce_bytes)
from repro.apps.metrics import run_phased, step_time
from repro.configs.base import get_config
from repro.core import fattree
from repro.core.engine import make_engine
from repro.core.workload import Workload

CFG = get_config("deepseek_v3")
SMOKE = get_config("deepseek_v3", smoke=True)

#: https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json
CONFIG_JSON = {
    "first_k_dense_replace": 3, "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "moe_intermediate_size": 2048, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "topk_group": 4,
    "v_head_dim": 128, "vocab_size": 129280,
}
FIELDS = {
    "first_k_dense_replace": "n_dense_layers", "hidden_size": "d_model",
    "intermediate_size": "dense_d_ff", "kv_lora_rank": "kv_lora_rank",
    "moe_intermediate_size": "moe_d_ff", "n_group": "n_group",
    "n_routed_experts": "n_experts", "n_shared_experts": "n_shared_experts",
    "num_attention_heads": "n_heads", "num_experts_per_tok": "top_k",
    "num_hidden_layers": "n_layers", "num_key_value_heads": "n_kv_heads",
    "num_nextn_predict_layers": "n_mtp_layers", "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "topk_group": "topk_group",
    "v_head_dim": "v_head_dim", "vocab_size": "vocab_size",
}


def test_config_holds_config_json():
    for key, field in FIELDS.items():
        assert getattr(CFG, field) == CONFIG_JSON[key], key
    assert CFG.pattern == (("attn", "moe"),) and CFG.router_bias
    assert CFG.n_blocks == 58                      # 61 less 3 dense
    assert SMOKE.family == CFG.family and SMOKE.n_blocks == 2


def _tensors(c: dict) -> dict:
    """DeepSeek-V3's tensors by their names in the published weights,
    from config.json's keys: {name: parameters of one such tensor}."""
    d, h, v = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    e, f = c["n_routed_experts"], c["moe_intermediate_size"]
    return {
        "embed_tokens": v * d, "lm_head": d * v, "norm": d,
        "input_layernorm": d, "post_attention_layernorm": d,
        "q_a_proj": d * c["q_lora_rank"],
        "q_a_layernorm": c["q_lora_rank"],
        "q_b_proj": c["q_lora_rank"] * h * qk,
        "kv_a_proj_with_mqa": d * (c["kv_lora_rank"]
                                   + c["qk_rope_head_dim"]),
        "kv_a_layernorm": c["kv_lora_rank"],
        "kv_b_proj": c["kv_lora_rank"] * h
        * (c["qk_nope_head_dim"] + c["v_head_dim"]),
        "o_proj": h * c["v_head_dim"] * d,
        "dense_mlp": 3 * d * c["intermediate_size"],
        "gate": e * d, "e_score_correction_bias": e,
        "expert": 3 * d * f, "shared_experts": c["n_shared_experts"] * 3 * d * f,
        "enorm": d, "hnorm": d, "eh_proj": 2 * d * d, "shared_head.norm": d,
    }


def _written_out(c: dict, mtp: bool = True) -> int:
    t = _tensors(c)
    attn = sum(t[k] for k in ("q_a_proj", "q_a_layernorm", "q_b_proj",
                              "kv_a_proj_with_mqa", "kv_a_layernorm",
                              "kv_b_proj", "o_proj"))
    norms = t["input_layernorm"] + t["post_attention_layernorm"]
    dense = attn + norms + t["dense_mlp"]
    moe = (attn + norms + t["gate"] + t["e_score_correction_bias"]
           + c["n_routed_experts"] * t["expert"] + t["shared_experts"])
    n_dense = c["first_k_dense_replace"]
    total = (t["embed_tokens"] + t["lm_head"] + t["norm"] + n_dense * dense
             + (c["num_hidden_layers"] - n_dense) * moe)
    if mtp:    # embedding and head shared with the main model
        total += c["num_nextn_predict_layers"] * (
            t["enorm"] + t["hnorm"] + t["eh_proj"] + moe
            + t["shared_head.norm"])
    return total


def _smoke_json() -> dict:
    c = dict(CONFIG_JSON)
    for key, field in FIELDS.items():
        c[key] = getattr(SMOKE, field)
    return c


@pytest.mark.parametrize("cfg,c", [(CFG, CONFIG_JSON),
                                   (SMOKE, _smoke_json())],
                         ids=["config", "smoke"])
def test_param_count_is_the_written_out_count(cfg, c):
    assert param_count(cfg) == _written_out(c)
    assert param_count(cfg.replace(n_mtp_layers=0)) \
        == _written_out(c, mtp=False)


def test_param_count_matches_the_published_totals():
    main = param_count(CFG.replace(n_mtp_layers=0))
    assert main == 671_026_419_200
    assert abs(main / 671e9 - 1) < 0.005
    # activated a token: every layer's attention and norms, the dense
    # FFNs, the shared and top-8 routed experts, the router, the head;
    # the embedding is a lookup.  The published 37B is rounded to the
    # billion, so the check is to the nearest billion, not 0.5%.
    t = _tensors(CONFIG_JSON)
    routed_idle = 58 * (256 - 8) * t["expert"]
    active = main - routed_idle - t["embed_tokens"]
    assert active == 36_625_618_432
    assert round(active / 1e9) == 37
    # the MTP module: 11.6B of its own, 13.5B with the shared embedding
    # and head it uses (the technical report's "14B")
    mtp = param_count(CFG) - main
    assert mtp == 11_610_068_224
    assert round((mtp + t["embed_tokens"] + t["lm_head"]) / 1e9, 1) == 13.5


def test_model_layer_refuses_what_it_cannot_build():
    """The adapted model layer has no MLA, leading dense layers, shared
    experts or MTP, so SMOKE cannot be checked against
    ``count_params(model_defs(...))``; the count above stands in."""
    from repro.models.model import model_defs, supports
    assert not supports(SMOKE)
    with pytest.raises(NotImplementedError, match="MLA"):
        model_defs(SMOKE)


def test_kv_cache_and_tp_units_count_mla_and_dense_layers():
    # MLA caches the 512-d latent and the 64-d rope key, per layer
    assert kv_cache_bytes(CFG, 4096) == 61 * 4096 * (512 + 64) * 2
    act = 8 * 64 * SMOKE.d_model * 2
    # 2 MoE layers: the mixer (experts on the model axis reduce in the
    # a2a); 1 dense layer: mixer + FFN
    assert tp_allreduce_bytes(SMOKE, 64, 8, 2) == (2 * 1 + 2) * act * 2


# ----------------------------------------------------------------- router

def _loop(scores: np.ndarray, n_group: int, topk_group: int,
          top_k: int) -> list:
    """The published rule, token by token."""
    out = []
    per = scores.shape[1] // n_group
    for row in scores:
        v = [float(a) for a in row]
        gs = []
        for g in range(n_group):
            s = 0.0
            for a in sorted(v[g * per:(g + 1) * per])[-(top_k // topk_group):]:
                s += a
            gs.append(s)
        kept = sorted(range(n_group), key=lambda g: (-gs[g], g))[:topk_group]
        cand = [e for g in sorted(kept) for e in range(g * per, (g + 1) * per)]
        best = sorted(cand, key=lambda e: (-v[e], e))[:top_k]
        out.append(sum(1 << g for g in {e // per for e in best}))
    return out


@pytest.mark.parametrize("case", ["dsv3", "ties"])
def test_router_equals_a_per_token_loop(case):
    rng = np.random.default_rng(1234)
    if case == "dsv3":
        scores = rng.random((2000, 256), np.float32)
        g, k_g, k = 8, 4, 8
    else:   # six levels: many ties inside and across groups
        scores = (np.floor(rng.random((2000, 16)) * 6) / 6).astype(
            np.float32)
        g, k_g, k = 4, 2, 4
    got = node_limited_sets(scores, g, k_g, k)
    assert got.tolist() == _loop(scores, g, k_g, k)
    # at most topk_group nodes, at least one
    n = np.array([bin(m).count("1") for m in got])
    assert n.min() >= 1 and n.max() <= k_g


def test_route_ep_groups_is_seeded_and_counts_every_token():
    a = route_ep_groups(SMOKE, 8, 64, [(7, 0), (7, 1)])
    b = route_ep_groups(SMOKE, 8, 64, [(7, 1)])
    assert a[1].tolist() == b[0].tolist()
    assert a[0].tolist() != a[1].tolist()
    assert a[0].shape == (8, 1 << SMOKE.n_group)
    assert (a[0].sum(1) == 64).all() and a[0][:, 0].sum() == 0
    with pytest.raises(ValueError, match="node-limited"):
        route_ep_groups(SMOKE, 6, 64, [0])


# ------------------------------------------------------- dispatch, combine

def test_token_bytes():
    assert dispatch_token_bytes(CFG) == 7168 + 4 * 56 == 7392
    assert combine_token_bytes(CFG) == 14336
    assert dispatch_token_bytes(SMOKE) == 64 + 4


def test_dispatch_and_combine_sizes_equal_a_hand_table():
    """4 ranks on 2 nodes of 2 GPUs; rank e is GPU e % 2 of node e // 2.
    Each row: a rank's tokens per target-node set (bit 0 node 0)."""
    hist = np.array([[0, 5, 3, 2],       # rank 0, node 0
                     [0, 4, 0, 0],       # rank 1, node 0: stays home
                     [0, 1, 6, 0],       # rank 2, node 1
                     [0, 0, 0, 7]])      # rank 3, node 1
    assert node_token_counts(hist).tolist() == [[7, 5], [4, 0],
                                                [1, 6], [7, 7]]
    group = ["a0", "a1", "b0", "b1"]
    d, c, s = 68, 128, 3                 # SMOKE's token bytes, scale 3
    want_c = [(("b0", "a0"), 5 * c * s), (("a0", "b0"), 1 * c * s),
              (("a1", "b1"), 7 * c * s)]
    mu = Workload("mu")
    moe_ep_ops(mu, SMOKE, hist, group, s, "multiunicast")
    got = [(o.op, o.members, o.nbytes, o.phase) for o in mu.ops]
    assert got == [("unicast", ("a0", "b0"), 5 * d * s, "moe-dispatch"),
                   ("unicast", ("b0", "a0"), 1 * d * s, "moe-dispatch"),
                   ("unicast", ("b1", "a1"), 7 * d * s, "moe-dispatch")] \
        + [("unicast", m, n, "moe-combine") for m, n in want_c]
    gl = Workload("gl")
    moe_ep_ops(gl, SMOKE, hist, group, s, "gleam")
    got = [(o.op, o.members, o.nbytes, o.transport) for o in gl.ops]
    # rank 0's sets {1} and {0, 1} share one remote set: 3 + 2 tokens
    assert got[:3] == [("bcast", ("a0", "b0"), 5 * d * s, "gleam"),
                       ("bcast", ("b0", "a0"), 1 * d * s, "gleam"),
                       ("bcast", ("b1", "a1"), 7 * d * s, "gleam")]
    assert [(o.members, o.nbytes) for o in gl.ops[3:]] == want_c


def test_gleam_multicast_reaches_every_remote_target_node_once():
    hist = np.zeros((8, 16), np.int64)
    hist[1, 0b1011] = 9                  # rank 1 (node 0) to nodes 1, 3
    wl = Workload("g")
    moe_ep_ops(wl, SMOKE, hist, [f"h{i}" for i in range(8)], 1, "gleam")
    assert [(o.members, o.nbytes) for o in wl.ops
            if o.phase == "moe-dispatch"] == [(("h1", "h3", "h7"), 9 * 68)]
    assert sorted(o.members for o in wl.ops if o.phase == "moe-combine") \
        == [("h3", "h1"), ("h7", "h1")]


# ------------------------------------------------------------ the step

@pytest.fixture(scope="module")
def full_step():
    hosts = rail_hosts(fattree.fat_tree(
        n_pods=8, leaves_per_pod=8, hosts_per_leaf=32,
        aggs_per_pod=16).hosts, 8)
    mesh = MeshShape(pipe=16, data=128, expert=64)
    wl = train_step_workload(CFG, mesh, hosts, seq=4096, batch=15360,
                             accum=120, transport="multiunicast")
    return wl, hosts


def _plane(host: str) -> str:
    return host.split(".")[0]


def _node(host: str) -> tuple:
    return tuple(host.split(".")[1:])


def test_full_step_phases_and_sizes(full_step):
    wl, hosts = full_step
    ops = Counter(o.phase for o in wl.ops)
    # stage 0 holds the three dense layers only: 15 stages x 2 EP
    # groups x 64 ranks x 7 remote nodes
    assert stage_layers(CFG, 16)[0] == [("attn", "dense")] * 3
    assert ops["moe-dispatch"] == ops["moe-combine"] == 15 * 2 * 64 * 7
    assert ops["pp-boundary"] == 15 * 128
    assert ops["dp-gradsync"] == 16 + 15 * 64
    assert set(ops) == {"moe-dispatch", "moe-combine", "pp-boundary",
                        "dp-gradsync"}
    for o in wl.ops:
        if o.phase.startswith("moe"):
            a, b = o.members
            assert _plane(a) == _plane(b) and _node(a) != _node(b)
    # one micro-batch x 4,096 tokens crosses each cut, 120 of them,
    # forward and backward
    pp = {o.nbytes for o in wl.ops if o.phase == "pp-boundary"}
    assert pp == {4096 * 7168 * 2 * 120 * 2}
    sync = [o for o in wl.ops if o.phase == "dp-gradsync"]
    # stage 0: 3 dense layers + the embedding, f32, over 128 ranks
    assert (sync[0].nbytes, len(sync[0].members)) \
        == (F32 * (3 * 583_483_392 + 926_679_040), 128)
    # stage 1: four MoE layers outside their routed experts, then one
    # EP rank's 4 experts of each layer over the 2 EP groups
    assert (sync[1].nbytes, len(sync[1].members)) \
        == (F32 * 4 * (11_507_286_272 - 256 * 44_040_192), 128)
    assert (sync[2].nbytes, len(sync[2].members)) \
        == (F32 * 4 * 4 * 44_040_192, 2)
    assert sync[2].members == (hosts[128 + 0], hosts[128 + 64])


def test_full_step_dispatch_matches_combine(full_step):
    wl, hosts = full_step
    n_moe = [sum(1 for _, f in s if f == "moe")
             for s in stage_layers(CFG, 16)]
    stage = {h: i // 128 for i, h in enumerate(hosts)}
    disp = {o.members: o.nbytes for o in wl.ops if o.phase == "moe-dispatch"}
    comb = {o.members[::-1]: o.nbytes for o in wl.ops
            if o.phase == "moe-combine"}
    assert disp.keys() == comb.keys()
    sent = Counter()
    for pair, nb in disp.items():
        per = 7392 * n_moe[stage[pair[0]]] * 120 * 2
        assert nb % per == 0
        tokens = nb // per
        assert comb[pair] == tokens * 14336 * n_moe[stage[pair[0]]] * 240
        # i.i.d. scores: about half of a rank's tokens reach each node
        assert 1700 < tokens < 2400
        sent[pair[0]] += tokens
    # a token goes to at most 4 nodes
    assert max(sent.values()) <= 4 * 4096


def test_expert_axis_validation():
    with pytest.raises(ValueError, match="does not divide"):
        MeshShape(data=12, expert=8)
    with pytest.raises(ValueError, match="tensor parallelism"):
        train_step_workload(SMOKE, MeshShape(data=8, model=2, expert=8),
                            seq=64, batch=8)
    with pytest.raises(ValueError, match="do not divide"):
        train_step_workload(SMOKE, MeshShape(data=6, expert=3),
                            seq=64, batch=6)
    assert MeshShape(data=8, expert=8).to_dict()["expert"] == 8
    assert "expert" not in MeshShape(data=8).to_dict()
    assert MeshShape.from_dict(MeshShape(data=8, expert=8).to_dict()) \
        == MeshShape(data=8, expert=8)


# md5 of every lowering below, taken on the tree before the expert axis
LEGACY = {
    "llama3_2_3b/smoke": "236875ae2f528999f2fa28111d92e4e1",
    "llama3_2_3b": "f2b21eb354a2de767c17b4a7bd0b463a",
    "mixtral_8x7b/smoke": "38c0e87166e0accca8db82a232f2c3e6",
    "mixtral_8x7b": "76fd07d05a8295f262b80ebc2fe4220e",
    "jamba_v0_1_52b/smoke": "7c5caf12b3b76e2e1b613f73ea6c5258",
    "jamba_v0_1_52b": "354588940b9532cb368a7a75e09d8dae",
    "qwen3_moe_235b_a22b/smoke": "5d0bd16dd192bef5e6d4e82e4d3c90d2",
    "qwen3_moe_235b_a22b": "47737db8daa31617145c35e1c91db9a0",
    "granite_3_2b/smoke": "2ca772d43f699c2cfdb6c854c25f9a7d",
    "granite_3_2b": "87c546045ff1e9dd84778bd80f9f034a",
    "qwen1_5_110b/smoke": "62b700c721c5cba442d38a4ed5b35768",
    "qwen1_5_110b": "779a272d8dfab1f611e6dbff79ac8e44",
    "mamba2_370m/smoke": "1094f06eee9fdac1854455d3e51b1b59",
    "mamba2_370m": "cb5f28191089e82598f5ef294ef176ca",
    "h2o_danube_3_4b/smoke": "29e847c3b37b3aa6e04bf5531378828d",
    "h2o_danube_3_4b": "83e318ea29ae5a7f443e314f5786a2ae",
}
MESHES = [dict(data=2, model=2), dict(data=2, model=2, pipe=2),
          dict(data=4, model=2), dict(data=2, model=4, pipe=2)]


@pytest.mark.parametrize("name", sorted(LEGACY))
def test_meshes_without_expert_axis_lower_as_before(name):
    arch, _, smoke = name.partition("/")
    cfg = get_config(arch, smoke=bool(smoke))
    h = hashlib.md5()
    for m in MESHES:
        for tr in ("gleam", "multiunicast"):
            try:
                wl = train_step_workload(cfg, MeshShape(**m), seq=64,
                                         batch=16, accum=2, transport=tr,
                                         include_ckpt=True)
                h.update(json.dumps(wl.to_dict(), sort_keys=True).encode())
            except ValueError as e:
                h.update(str(e).encode())
    wl = weight_bcast_workload(cfg, 4, 2)
    h.update(json.dumps(wl.to_dict(), sort_keys=True).encode())
    h.update(repr((param_count(cfg), kv_cache_bytes(cfg, 128),
                   tp_allreduce_bytes(cfg, 64, 8, 2))).encode())
    assert h.hexdigest() == LEGACY[name]


# ------------------------------------------------------- on the engines

def _ep_group(transport):
    """SMOKE's MoE phases on one EP group: 8 GPUs on 4 nodes of 2."""
    wl = train_step_workload(SMOKE, MeshShape(data=8, expert=8), seq=64,
                             batch=8, transport=transport)
    return Workload(wl.name, [o for o in wl.ops
                              if o.phase.startswith("moe")], meta=wl.meta)


@pytest.mark.parametrize("transport", ["multiunicast", "gleam"])
def test_packet_flow_parity_on_one_ep_group(transport):
    wl = _ep_group(transport)
    assert {o.phase for o in wl.ops} == {"moe-dispatch", "moe-combine"}
    out = {}
    for name in ("packet", "flow"):
        eng = make_engine(name, fattree.testbed(n_hosts=8))
        ops, recs = run_phased(eng, wl, timeout=120.0)
        out[name] = step_time(ops, recs)
    div = abs(out["packet"] - out["flow"]) / out["packet"]
    assert div <= 0.10, f"{transport}: {out} div={div:.1%}"


def test_gleam_dispatch_sends_fewer_bytes_per_source_nic():
    sent = {}
    for tr in ("multiunicast", "gleam"):
        sent[tr] = Counter()
        for o in _ep_group(tr).ops:
            if o.phase == "moe-dispatch":
                sent[tr][o.members[0]] += o.nbytes
    assert sent["gleam"].keys() == sent["multiunicast"].keys()
    assert len(sent["gleam"]) == 8
    for h, nb in sent["gleam"].items():
        assert nb < sent["multiunicast"][h]
