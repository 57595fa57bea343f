"""counts.py against a count made position by position at smoke widths,
for GQA and dense layers and for MLA and expert layers; the dense counts
of both benchmark configurations pinned."""

import json
import os

import pytest

import counts
from conftest import BENCH

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 192, "vocab_size": 256,
       "num_hidden_layers": 2}
STEP = {"chunks": [(0, 3), (256, 300)], "decodes": [5, 129],
        "promoted": [40], "emitted": 3}


def _bench_cfg(name):
    with open(os.path.join(BENCH, "configs", name)) as f:
        return json.load(f)


def _by_hand():
    D, H, KV, HD, FF, V, L = 64, 4, 2, 16, 192, 256, 2
    proj = D * H * HD + D * KV * HD * 2 + H * HD * D      # q, k, v, o
    mlp = 3 * D * FF                                      # gate, up, down
    flops = 0
    positions = [p for s, e in STEP["chunks"] for p in range(s, e)]
    positions += [k - 1 for k in STEP["decodes"]]
    for p in positions:
        for _ in range(L):
            flops += 2 * (proj + mlp)
            flops += 2 * (p + 1) * H * HD * 2             # q.k and p.v
    flops += STEP["emitted"] * 2 * D * V
    return flops


def test_model_flops_by_hand():
    assert counts.model_flops(counts.dims(CFG), STEP) == _by_hand()


def test_kernel_counts_by_hand():
    d = counts.dims(CFG)
    block = 2 * 2 * 128 * 16 * 2          # K and V, 2 heads, 128 tokens
    qo = 2 * 4 * 16 * 2                   # one query in, one output out
    # ragged: chunk 0..3 (1 block), chunk 256..300 (3 blocks), decodes
    # of 5 and 129 keys (1 and 2 blocks)
    pairs = (1 + 2 + 3) + sum(range(257, 301)) + 5 + 129
    f, b = counts.ragged_kernel(d, STEP)
    assert f == 2 * 4 * 4 * 16 * pairs
    assert b == 2 * ((1 + 3 + 1 + 2) * block + (3 + 44 + 1 + 1) * qo)
    # decode: the two decode rows and the promoted row of 40 keys
    f, b = counts.decode_kernel(d, STEP)
    assert f == 2 * 4 * 4 * 16 * (5 + 129 + 40)
    assert b == 2 * ((1 + 2 + 1) * block + 3 * qo)


def test_least_seconds_names_the_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000, 10, peak) == (10.0, "compute")
    assert counts.least_seconds(10, 1000, peak) == (100.0, "memory")


# =============================================== MLA and expert layers
#: smoke widths: MLA with compressed queries, one dense layer then two
#: expert layers, 8 of 16 experts held here
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128,
         "vocab_size": 256, "num_hidden_layers": 3,
         "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8,
         "qk_nope_head_dim": 16, "v_head_dim": 16,
         "first_k_dense_replace": 1, "n_routed_experts": 8,
         "num_experts_per_tok": 2, "n_shared_experts": 1,
         "moe_intermediate_size": 48,
         "published": {"n_routed_experts": 16}}
def _mla_by_hand(cfg, step):
    D, H, V = 64, 4, 256
    QR, KVR, ROPE, NOPE, VD = 48, 32, 8, 16, 16
    FF, DE, held, experts, top_k, shared = 128, 48, 8, 16, 2, 1
    q_proj = (D * QR + QR * H * (NOPE + ROPE) if cfg["q_lora_rank"]
              else D * H * (NOPE + ROPE))
    mixer = (q_proj + D * (KVR + ROPE) + KVR * H * (NOPE + VD)
             + H * VD * D)
    expert = 3 * D * DE
    positions = [p for s, e in step["chunks"] for p in range(s, e)]
    positions += [k - 1 for k in step["decodes"]]
    flops = 0
    for p in positions:
        for layer in range(3):
            flops += 2 * mixer
            flops += 2 * H * ((NOPE + ROPE) + VD) * (p + 1)     # q.k, p.v
            if layer == 0:
                flops += 2 * 3 * D * FF
            else:
                flops += 2 * D * experts                        # router
                flops += 2 * shared * expert
                flops += 2 * expert * top_k * held / experts    # routed
    return flops + step["emitted"] * 2 * D * V


@pytest.mark.parametrize("q_lora_rank", [48, None])
def test_mla_expert_model_flops_by_hand(q_lora_rank):
    cfg = dict(SMALL, q_lora_rank=q_lora_rank)
    assert counts.model_flops(counts.dims(cfg), STEP) == _mla_by_hand(cfg, STEP)


def test_expert_pairs_counted_by_the_program_replace_the_share():
    d = counts.dims(SMALL)
    share = counts.model_flops(d, STEP)
    positions = 3 + 44 + 2
    step = dict(STEP, expert_pairs=5)
    assert counts.model_flops(d, step) == (
        share - positions * 2 * 1 * 2 * 3 * 64 * 48 + 5 * 2 * 3 * 64 * 48)


def test_latent_block_and_kernel_bytes_by_hand():
    d = counts.dims(SMALL)
    block = (32 + 8) * 128 * 2            # latent and rope key, 128 tokens
    assert counts.block_bytes(d) == block
    qo = 4 * (24 + 16) * 2                # one query in, one output out
    f, b = counts.decode_kernel(d, STEP)
    assert f == 3 * 2 * 4 * (24 + 16) * (5 + 129 + 40)
    assert b == 3 * ((1 + 2 + 1) * block + 3 * qo)


def test_layer_kinds_follow_the_file():
    assert counts.layer_kinds(SMALL) == [("mla", "dense"), ("mla", "moe"),
                                         ("mla", "moe")]
    every_other = dict(SMALL, num_hidden_layers=5, moe_layer_freq=2)
    assert [f for _, f in counts.layer_kinds(every_other)] == [
        "dense", "dense", "moe", "dense", "moe"]
    assert counts.layer_kinds(_bench_cfg("granite-3-8b-16L.json")) == [
        ("attn", "dense")] * 16



#: the parent's counts of both benchmark configurations on two fixed steps
#: (model flops, ragged kernel flops and bytes, decode kernel flops and
#: bytes), pinned: the dense counts may not move
FIXED = [{"chunks": [(0, 256), (1024, 1280)], "decodes": [1100, 1500, 2000],
          "promoted": [900], "emitted": 4},
         {"chunks": [], "decodes": [768, 2048, 3000, 129], "promoted": [],
          "emitted": 4}]
PINNED = {
    "granite-3-8b-16L.json": [
        (3372092489728, (87172317184, 546045952), (1441792000, 378535936)),
        (28678815744, (1558446080, 403701760), (1558446080, 403701760))],
    "deepseek-7b-8L.json": [
        (1714512986112, (43586158592, 889585664), (720896000, 755499008)),
        (17086676992, (779223040, 805830656), (779223040, 805830656))]}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_counts_are_the_parents(name):
    d = counts.dims(_bench_cfg(name))
    got = [(counts.model_flops(d, s), counts.ragged_kernel(d, s),
            counts.decode_kernel(d, s)) for s in FIXED]
    assert got == PINNED[name]
    assert all(isinstance(g[0], int) for g in got)
