"""PartitionSpec rules: parameters (by leaf path), batches, and KV/recurrent
caches (per family). These are the *baseline* sharding used by every
dry-run; perf iterations override pieces of them (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

_REPLICATED_LEAVES = {
    "ln", "ln1", "ln2", "ln_c", "w", "b", "q_norm", "k_norm", "b_in",
    "b_if", "lam", "final_norm", "enc_norm", "r", "w_r", "w_i",
    "conv_b", "b_r", "b_i", "pos", "count",
}


def param_spec(cfg: ModelConfig, path: str, ndim: int) -> P:
    parts = path.split("/")
    leaf = parts[-1]
    pre = (None,) * max(ndim - 2, 0)     # leading stack dims (group/layer)

    if leaf == "embed":
        return P("model", None)
    if leaf == "unembed":
        return P(None, "model")
    if leaf in _REPLICATED_LEAVES:
        return P(*(None,) * ndim)
    if leaf in ("wi", "wg", "wo") and ndim >= 4 and cfg.n_experts > 0:
        # stacked MoE expert weights (G, E, D, F) / (G, E, F, D): expert-parallel
        return P(*(None,) * (ndim - 3), "model", None, None)
    if leaf == "router":
        return P(*pre, None, "model")
    if leaf in ("wq", "wk", "wv", "w_up", "w_gate", "w_in", "wi", "wg"):
        return P(*pre, None, "model")
    if leaf in ("wo", "w_down", "w_out", "w_if"):
        return P(*pre, "model", None)
    if leaf in ("bq", "bk", "bv"):
        return P(*(None,) * (ndim - 1), "model")
    if leaf == "conv_w":
        return P(*pre, None, "model")
    return P(*(None,) * ndim)


def _path_str(path) -> str:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(out)


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop mesh-axis assignments that do not divide the dimension size
    (e.g. a 51866-token vocab over a 16-way model axis)."""
    out = []
    for d, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        out.append(entry if shape[d] % prod == 0 else None)
    return P(*out)


def param_specs(cfg: ModelConfig, abstract_params, mesh=None):
    """PartitionSpec tree matching an (abstract) param tree."""
    def one(path, leaf):
        spec = param_spec(cfg, _path_str(path), leaf.ndim)
        return sanitize_spec(spec, leaf.shape, mesh) if mesh is not None else spec
    return jax.tree_util.tree_map_with_path(one, abstract_params)


def param_shardings(cfg, abstract_params, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(cfg, abstract_params, mesh))


def param_specs_fsdp(abstract_params, mesh, axes=("data", "model")):
    """ZeRO-3 storage sharding: every weight sharded over the flattened
    (data, model[, pod]) axes on its largest divisible dim. Compute-time
    re-gathering is done per layer via ``maybe_gather_params``."""
    if "pod" in mesh.axis_names:
        axes = ("pod",) + tuple(axes)
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def one(leaf):
        dims = sorted(range(leaf.ndim), key=lambda d: -leaf.shape[d])
        for d in dims:
            if leaf.shape[d] % n == 0:
                spec = [None] * leaf.ndim
                spec[d] = tuple(axes)
                return P(*spec)
        return P(*(None,) * leaf.ndim)

    return jax.tree.map(one, abstract_params)


def param_shardings_fsdp(abstract_params, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs_fsdp(abstract_params, mesh))


# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, abstract_batch, batch_axes: Tuple[str, ...]):
    b = batch_axes if batch_axes else None
    specs = {}
    for k, v in abstract_batch.items():
        specs[k] = P(b, *(None,) * (v.ndim - 1))
    return specs


# ---------------------------------------------------------------------------
# cache specs (mirror each family's init_cache structure)
# ---------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, abstract_cache, batch_axes: Tuple[str, ...],
                kv_seq_axis: Optional[str] = None):
    """kv_seq_axis: mesh axis to shard the KV sequence dim over (long-KV
    decode optimization); None = unsharded."""
    b = batch_axes if batch_axes else None

    def kv(a):   # (G/L, B, S, K, H) or (G/L, B, S, K * H)
        return P(None, b, kv_seq_axis, *(None,) * (a.ndim - 3))

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return {
            "slots": tuple({"k": kv(s["k"]), "v": kv(s["v"])}
                           for s in abstract_cache["slots"]),
            "pos": P(),
        }
    if fam == "audio":
        return {"k": kv(abstract_cache["k"]), "v": kv(abstract_cache["v"]),
                "ck": P(None, b, None, None, None),
                "cv": P(None, b, None, None, None), "pos": P()}
    if fam == "ssm":
        # slots: mLSTM (C,n,m) or sLSTM (c,n,m,h); every leaf is (G,B,...)
        def leaf_spec(a):
            return P(None, b, *(None,) * (a.ndim - 2))
        return {
            "slots": jax.tree.map(leaf_spec, abstract_cache["slots"]),
            "pos": P(),
        }
    if fam == "hybrid":
        def slot_spec(slot, stacked: bool):
            n = 1 if stacked else 0
            if isinstance(slot, dict):      # attention: k/v (G?,B,S,K[,H])
                return {name: P(*(None,) * n, b, *(None,) * (a.ndim - n - 1))
                        for name, a in slot.items()}
            # rec: (conv (G?,B,cw-1,W), h (G?,B,W))
            return (P(*(None,) * n, b, None, "model"),
                    P(*(None,) * n, b, "model"))
        return {
            "slots": tuple(slot_spec(s, True) for s in abstract_cache["slots"]),
            "rest": tuple(slot_spec(s, False) for s in abstract_cache["rest"]),
            "pos": P(),
        }
    raise ValueError(fam)


def cache_shardings(cfg, abstract_cache, mesh, batch_axes, kv_seq_axis=None):
    specs = cache_specs(cfg, abstract_cache, batch_axes, kv_seq_axis)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
