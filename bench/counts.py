"""Operations and bytes of the model step and of each paged-attention
kernel, from a configuration's sizes and the rows each step really
served.

A step's work is described by the harness as

* ``chunks``: ``(start, end)`` prompt positions a prefill chunk wrote;
* ``decodes``: for each decode row that fed back a generated token, the
  number of keys it attended (the position written plus one);
* ``promoted``: on the per-slot path, rows that emit their first token by
  rerunning the prompt's last position (keys attended); this is kernel
  work, but no new position of the model;
* ``emitted``: tokens emitted;
* ``expert_pairs`` (where the program counts them): token-expert pairs
  that the experts held on this chip computed, over all layers.

Counts are those of the algorithm in bf16: two operations per
multiply-add, causal attention over the keys each query sees, each
resident KV block read once per row and layer.  Padding rows and the
extra passes of ``Precision.HIGHEST`` are not counted.

Each kind of layer the configuration file implies (:func:`layer_kinds`)
counts at its own sizes: GQA attention, or MLA (DeepSeek-V2: query and
latent projections, the latent's up-projection once per new position,
attention at query-key width nope + rope and value width ``v_head_dim``,
a pool block of latent + rope key); a dense MLP at ``intermediate_size``,
or an expert layer (router over every expert, shared experts, and the
token-expert pairs of the experts this chip holds).
"""

from __future__ import annotations

BLOCK_TOKENS = 128
BYTES = 2                      # bf16 weights, activations and KV cache


def layer_kinds(cfg: dict) -> list:
    """(mixer, ffn) of each layer, as the configuration file implies them:
    ``mla`` where it has ``kv_lora_rank``, else ``attn``; where it has
    ``n_routed_experts``, ``first_k_dense_replace`` dense layers and then
    ``moe`` on every ``moe_layer_freq``-th layer, else ``dense``."""
    mixer = "mla" if "kv_lora_rank" in cfg else "attn"
    first = cfg.get("first_k_dense_replace", 0)
    every = cfg.get("moe_layer_freq", 1)

    def ffn(i):
        moe = "n_routed_experts" in cfg and i >= first and i % every == 0
        return "moe" if moe else "dense"
    return [(mixer, ffn(i)) for i in range(cfg["num_hidden_layers"])]


def dims(cfg: dict) -> dict:
    """The sizes the counts take.  ``QK``/``VD`` are the query-key and
    value widths of a head; an MLA layer adds its ranks (``QR`` 0 where
    queries are not compressed), an expert layer its experts: ``E`` held
    on this chip of the router's ``E_ALL`` (the published count)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kinds = layer_kinds(cfg)
    out = {"D": d, "H": h, "KV": cfg["num_key_value_heads"],
           "HD": cfg.get("head_dim", d // h), "FF": cfg["intermediate_size"],
           "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
           "MLA": kinds[0][0] == "mla",
           "MOE_LAYERS": sum(f == "moe" for _, f in kinds)}
    out["QK"] = out["VD"] = out["HD"]
    if out["MLA"]:
        out.update(QR=cfg["q_lora_rank"] or 0, KVR=cfg["kv_lora_rank"],
                   ROPE=cfg["qk_rope_head_dim"], NOPE=cfg["qk_nope_head_dim"],
                   VD=cfg["v_head_dim"])
        out["QK"] = out["NOPE"] + out["ROPE"]
    if out["MOE_LAYERS"]:
        held = cfg["n_routed_experts"]
        out.update(E=held, E_ALL=cfg.get("published", {}).get(
            "n_routed_experts", held), TOPK=cfg["num_experts_per_tok"],
            SHARED=cfg.get("n_shared_experts") or 0,
            DE=cfg["moe_intermediate_size"])
    return out


def _keys(start: int, end: int) -> int:
    """Keys attended by queries at positions start..end-1 together."""
    return (end * (end + 1) - start * (start + 1)) // 2


def mixer_flops(d: dict) -> int:
    """Projection operations of one layer's mixer for one position."""
    D, H = d["D"], d["H"]
    if not d["MLA"]:
        KV, HD = d["KV"], d["HD"]
        return 2 * (D * H * HD + 2 * D * KV * HD + H * HD * D)
    q = (D * d["QR"] + d["QR"] * H * d["QK"] if d["QR"]
         else D * H * d["QK"])
    kv = D * (d["KVR"] + d["ROPE"]) + d["KVR"] * H * (d["NOPE"] + d["VD"])
    return 2 * (q + kv + H * d["VD"] * D)


def expert_flops(d: dict) -> int:
    """Operations of one expert, routed or shared (gate, up, down), for
    one token."""
    return 2 * 3 * d["D"] * d["DE"]


def ffn_flops(d: dict) -> int:
    """Feed-forward operations of all layers for one position, but for
    the routed experts: the dense layers at their own width, and each
    expert layer's router over every expert and its shared experts."""
    D = d["D"]
    dense = (d["L"] - d["MOE_LAYERS"]) * 2 * 3 * D * d["FF"]
    if not d["MOE_LAYERS"]:
        return dense
    per_moe = 2 * D * d["E_ALL"] + d["SHARED"] * expert_flops(d)
    return dense + d["MOE_LAYERS"] * per_moe


def attn_flops(d: dict, keys: int) -> int:
    """Score and value products of all heads over ``keys`` query-key
    pairs, in one layer."""
    return 2 * d["H"] * (d["QK"] + d["VD"]) * keys


def block_bytes(d: dict) -> int:
    """One pool block of one layer: fused K+V, or an MLA layer's latent
    and rope key."""
    if d["MLA"]:
        return (d["KVR"] + d["ROPE"]) * BLOCK_TOKENS * BYTES
    return 2 * d["KV"] * BLOCK_TOKENS * d["HD"] * BYTES


def model_flops(d: dict, step: dict) -> int:
    """Operations of the model for the new positions and emitted tokens
    of one step.  The routed experts count the token-expert pairs the
    chip's held experts compute: ``expert_pairs`` where the step carries
    the program's own count over all layers, else ``num_experts_per_tok``
    times the held share of the experts, a position and an expert
    layer."""
    positions = sum(e - s for s, e in step["chunks"]) + len(step["decodes"])
    keys = (sum(_keys(s, e) for s, e in step["chunks"])
            + sum(step["decodes"]))
    flops = (positions * (d["L"] * mixer_flops(d) + ffn_flops(d))
             + d["L"] * attn_flops(d, keys)
             + step["emitted"] * 2 * d["D"] * d["V"])
    if d["MOE_LAYERS"]:
        pairs = step.get("expert_pairs", positions * d["MOE_LAYERS"]
                         * d["TOPK"] * d["E"] / d["E_ALL"])
        flops += pairs * expert_flops(d)
    return flops


def _kernel(d: dict, rows: list) -> tuple[int, int]:
    """rows: (queries, query-key pairs, keys of the row) → (flops, bytes)
    over all layers."""
    flops = sum(attn_flops(d, pairs) for _, pairs, _ in rows)
    nbytes = sum(-(-keys // BLOCK_TOKENS) * block_bytes(d)
                 + q * d["H"] * (d["QK"] + d["VD"]) * BYTES
                 for q, _, keys in rows)
    return d["L"] * flops, d["L"] * nbytes


def ragged_kernel(d: dict, step: dict) -> tuple[int, int]:
    """The ragged kernel's work in one step: every chunk and decode row."""
    rows = [(e - s, _keys(s, e), e) for s, e in step["chunks"]]
    rows += [(1, k, k) for k in step["decodes"]]
    return _kernel(d, rows)


def decode_kernel(d: dict, step: dict) -> tuple[int, int]:
    """The fused decode kernel's work in one step: one query per decoding
    row."""
    return _kernel(d, [(1, k, k) for k in step["decodes"] + step["promoted"]])


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of the compute and the memory time."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
