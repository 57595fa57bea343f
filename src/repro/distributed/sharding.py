"""Sharding rules: DP / FSDP / TP / EP / SP specs for every pytree we ship.

Axis convention (launch/mesh.py):

    single-pod : mesh (16, 16)      axes ("data", "model")
    multi-pod  : mesh (2, 16, 16)   axes ("pod", "data", "model")

* ``model``            — tensor parallel (Megatron column/row) + expert
                         parallel (MoE expert dim) + KV-head parallel.
* ``data`` (+ ``pod``) — data parallel for activations, FSDP/ZeRO for
                         parameters and optimiser state.  Cross-pod gradient
                         reduction is hierarchical (reduce-scatter in pod,
                         all-reduce over pods) — XLA derives it from the
                         nested spec.
* serving decode       — KV pools shard over ``model`` (kv heads) and, for
                         the SP/flash-decode path, the *pool* (sequence)
                         dimension over ``data`` (distributed/collectives).

Rules are by leaf *path* through the params pytree, mirroring
models/transformer.init_params; scanned "body" stacks get a leading None.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

# ---------------------------------------------------------------- axis sets


def dp_axes(mesh) -> tuple:
    """Data-parallel axes: ("pod","data") on multi-pod, ("data",) otherwise."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def fsdp_spec_axes(mesh):
    ax = dp_axes(mesh)
    return ax if len(ax) > 1 else (ax[0] if ax else None)


# ------------------------------------------------------------- param rules

#: column-parallel: (d_in, d_out) → (FSDP, model)
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "s_gate", "s_up", "in_proj",
        "wq_b", "wkv_b", "wr", "wg", "dt_proj"}
#: row-parallel: (d_in, d_out) → (model, FSDP)
_ROW = {"wo", "w_down", "s_down", "out_proj"}
#: FSDP-only on dim 0 (output dim small/shared): (d_in, d_out) → (FSDP, None)
_FSDP0 = {"wq_a", "wkv_a", "x_proj", "w_lora_a", "router"}
#: replicated small params
_REPL = {"norm", "q_norm", "kv_norm", "final_norm", "mu", "u", "ln_x",
         "conv_b", "D", "bq", "bk", "bv", "enc_pos", "dec_pos", "conv_w",
         "w_lora_b", "A_log"}
#: unembed (V, D) → (model, FSDP): vocab-parallel loss (logsumexp = psum).
#: embed is D-sharded instead — a vocab-sharded gather makes GSPMD fully
#: rematerialise the table (involuntary-replication warning + 0.8 GB/chip).
_VOCAB = {"unembed"}
#: frontend stubs (D, D)
_FRONT = {"vision_proj", "audio_proj"}


def _leaf_spec(name: str, ndim: int, fsdp) -> P:
    if name == "embed":
        # V over model; lookups go through the explicit vocab-parallel
        # shard_map embed (distributed/collectives.py), not a GSPMD gather
        return P("model", None)
    if name in _VOCAB:
        return P("model", fsdp)
    if name in _FRONT:
        return P(fsdp, None)
    if name in _REPL:
        return P(*([None] * ndim))
    if name in _COL:
        if ndim == 3:                       # MoE experts (E, d_in, d_out)
            return P("model", fsdp, None)
        if ndim == 1:                       # bias of a column-parallel proj
            return P("model")
        return P(fsdp, "model")
    if name in _ROW:
        if ndim == 3:                       # MoE (E, d_in, d_out)
            return P("model", None, fsdp)
        return P("model", fsdp)
    if name in _FSDP0:
        return P(fsdp, *([None] * (ndim - 1)))
    return P(*([None] * ndim))              # safe default: replicate


def _path_names(path) -> list[str]:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(f"[{k.idx}]")
    return out


def param_specs(params, mesh) -> dict:
    """PartitionSpec pytree matching ``params`` (from init_params)."""
    fsdp = fsdp_spec_axes(mesh)

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1]
        stacked = "body" in names           # scan-stacked: leading n_blocks
        nd = leaf.ndim - (1 if stacked else 0)
        spec = _leaf_spec(name, nd, fsdp)
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(rule, params)


def param_shardings(params, mesh) -> dict:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh))


# ------------------------------------------------------------ batch specs

def batch_specs(mesh, *, has_patches=False, has_frames=False) -> dict:
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    d = {"tokens": P(dp, None), "labels": P(dp, None)}
    if has_patches:
        d["patches"] = P(dp, None, None)
    if has_frames:
        d["frames"] = P(dp, None, None)
    return d


# --------------------------------------------------------- decode state specs

def decode_axes(mesh, *, batch: int):
    """(batch_axes, seq_axes) for the uniform decode layout.

    The pool N dim shards over batch_axes + seq_axes (row-major, matching
    transformer.sp_identity_tables); SP attention LSE-combines over
    seq_axes.  Batch absorbs the data(+pod) axes when divisible
    (decode_32k: 128 % 16 == 0); otherwise (long_500k: batch 1) the data
    axes join the sequence shards.  'model' always shards sequence —
    never KV heads, so no kv/mesh divisibility constraint exists.
    """
    dp = dp_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if batch % max(n_dp, 1) == 0 and n_dp > 1:
        return dp, ("model",)
    return (), dp + ("model",)


def decode_state_specs(cfg, mesh, *, batch_axes, seq_axes) -> dict:
    """Specs for the decode-state pytree (transformer.cache_spec keys)."""
    ba = tuple(batch_axes)
    pool = ba + tuple(seq_axes)
    pool = pool if len(pool) != 1 else pool[0]
    b = ba if len(ba) != 1 else (ba[0] if ba else None)
    sp: dict[str, P] = {}
    sp["tables"] = P(b, None)
    sp["lengths"] = P(b)
    # paged pools: (L, N, 2, KV, bs, hd) fused / (L, N, bs, rank)
    sp["kv"] = P(None, pool, None, None, None, None)
    sp["mla_c"] = P(None, pool, None, None)
    sp["mla_rope"] = P(None, pool, None, None)
    # recurrent states: (L, B, ...) — batch over ba, channels over model
    sp["conv"] = P(None, b, None, "model")
    sp["ssm"] = P(None, b, "model", None)
    sp["rwkv_x"] = P(None, b, "model")
    sp["rwkv_s"] = P(None, b, "model", None, None)
    sp["cross_k"] = P(None, b, None, None, None)
    sp["cross_v"] = P(None, b, None, None, None)
    return sp


def tokens_spec(mesh, *, shard_batch: bool = True) -> P:
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    return P(dp if shard_batch else None)


def filter_state_specs(specs: dict, state: dict) -> dict:
    """Keep only the spec entries whose key exists in the state pytree."""
    return {k: specs[k] for k in state}
