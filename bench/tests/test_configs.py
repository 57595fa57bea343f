"""The harness takes configurations with latent attention (MLA) and routed
experts: ``run.program_config`` checks every key a file has against the
program's config, refuses a file that disagrees, naming its key, and
builds the smoke config from the same keys; ``counts`` takes such a
file."""

import json
import os
from dataclasses import replace

import pytest

import counts
import run
from conftest import BENCH

from repro.configs import get_config, get_smoke

TESTS = os.path.join(BENCH, "tests", "configs")
MLA_MOE = "deepseek-v2-236b-4L.json"        # MLA + experts, cut to 4 layers
ATTN_MOE = "deepseek-moe-16b-5L.json"       # GQA + experts, cut to 5 layers
BENCHMARK = {"granite-3-8b-16L.json": "granite-3-8b",
             "deepseek-7b-8L.json": "deepseek-7b"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _test_cfg(name):
    return _load(os.path.join(TESTS, name))


def _bench_cfg(name):
    return _load(os.path.join(BENCH, "configs", name))


# ========================================================= program_config
@pytest.mark.parametrize("name", [MLA_MOE, ATTN_MOE])
def test_mla_and_expert_files_are_taken(name):
    cfg = _test_cfg(name)
    mc = run.program_config(cfg, smoke=False)
    assert mc == get_config(cfg["arch"], n_layers=cfg["num_hidden_layers"])
    mixer = "mla" if "kv_lora_rank" in cfg else "attn"
    assert mc.mixers == (mixer,) * cfg["num_hidden_layers"]
    assert mc.ffns == ("dense",) + ("moe",) * (cfg["num_hidden_layers"] - 1)
    d = counts.dims(cfg)                # and its counts are computed
    step = {"chunks": [(0, 256)], "decodes": [1100], "promoted": [900],
            "emitted": 2}
    assert counts.model_flops(d, step) > 0
    assert all(x > 0 for x in counts.ragged_kernel(d, step)
               + counts.decode_kernel(d, step))
    if mixer == "mla":                  # 1,152 bytes a token and layer
        assert counts.block_bytes(d) == (512 + 64) * 2 * 128


@pytest.mark.parametrize("key,value", [
    ("kv_lora_rank", 256), ("q_lora_rank", None), ("qk_rope_head_dim", 32),
    ("qk_nope_head_dim", 64), ("v_head_dim", 64), ("n_routed_experts", 64),
    ("num_experts_per_tok", 8), ("n_shared_experts", 1),
    ("moe_intermediate_size", 1408), ("intermediate_size", 10944),
    ("first_k_dense_replace", 2), ("moe_layer_freq", 2)])
def test_a_changed_mla_or_expert_key_is_refused_by_name(key, value):
    cfg = dict(_test_cfg(MLA_MOE), **{key: value})
    with pytest.raises(SystemExit, match=key):
        run.program_config(cfg, smoke=False)


def test_mixers_follow_kv_lora_rank():
    """A file without ``kv_lora_rank`` implies GQA layers, which the
    program's MLA model does not have; the attention-and-experts file with
    one implies MLA, which its program does not have."""
    cfg = _test_cfg(MLA_MOE)
    del cfg["kv_lora_rank"]
    with pytest.raises(SystemExit, match="kv_lora_rank"):
        run.program_config(cfg, smoke=False)
    cfg = dict(_test_cfg(ATTN_MOE), kv_lora_rank=512, q_lora_rank=None,
               qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128)
    with pytest.raises(SystemExit, match="kv_lora_rank"):
        run.program_config(cfg, smoke=False)


def test_the_key_table_builds_the_smoke_configs():
    """At smoke size the file's keys build the program's config: the MLA
    and expert sizes of the repository's own smoke config, which differs
    only in its name and its unused ``d_ff`` (each layer's width comes
    from ``dense_d_ff`` or ``d_expert``)."""
    for name, arch in ((MLA_MOE, "deepseek-v2-236b"),
                       (ATTN_MOE, "deepseek-moe-16b")):
        cfg = _test_cfg(name)
        mc = run.program_config(run._merge(cfg, cfg["smoke"]), smoke=True)
        want = get_smoke(arch)
        assert replace(mc, name=want.name, d_ff=want.d_ff) == want


@pytest.mark.parametrize("name", sorted(BENCHMARK))
def test_benchmark_files_pass_unchanged(name):
    """Both benchmark configurations give the program's own config, and at
    smoke size the config built from their keys, as before."""
    cfg = _bench_cfg(name)
    L = cfg["num_hidden_layers"]
    assert run.program_config(cfg, smoke=False) == get_config(
        BENCHMARK[name], n_layers=L)
    small = run._merge(cfg, cfg["smoke"])
    s = small["smoke"]
    want = replace(get_config(BENCHMARK[name], n_layers=s["num_hidden_layers"]),
                   d_model=s["hidden_size"], n_heads=s["num_attention_heads"],
                   n_kv_heads=s["num_key_value_heads"],
                   d_ff=s["intermediate_size"], vocab=s["vocab_size"],
                   head_dim=s["head_dim"], n_layers=s["num_hidden_layers"],
                   mixers=("attn",) * s["num_hidden_layers"],
                   ffns=("dense",) * s["num_hidden_layers"])
    assert run.program_config(small, smoke=True) == want
