"""Logical-axis sharding annotations (MaxText-style, minimal).

Model code annotates activations with *logical* axis names; a caller that
runs the model on a mesh installs a rules table mapping logical names ->
mesh axes (``use_rules``). Outside a rules context (the engine, one chip)
annotations are no-ops, so model code is written once and runs both
places.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

# logical axis -> mesh axes, installed by ``use_rules``
_RULES: Optional[Dict[str, MeshAxes]] = None
_MESH = None

# Canonical rule sets -------------------------------------------------------

def standard_rules() -> Dict[str, MeshAxes]:
    """2D (data, model) sharding."""
    batch = ("data",)
    return {
        "batch": batch,
        "seq": None,           # sequence replicated
        "d_model": None,       # activations replicated over model on entry
        "heads": "model",
        "kv_heads": None,      # GQA: kv heads usually < model axis -> replicate
        "d_ff": "model",
        "experts": "model",
        "vocab": "model",
        "kv_batch": batch,     # kv-cache batch dim
        "lru": "model",        # RG-LRU / mLSTM inner width
    }


@contextlib.contextmanager
def use_rules(rules: Dict[str, MeshAxes], mesh=None):
    global _RULES, _MESH
    prev, prev_mesh = _RULES, _MESH
    _RULES, _MESH = rules, mesh
    try:
        yield
    finally:
        _RULES, _MESH = prev, prev_mesh


def logical_to_spec(axes: Sequence[Optional[str]]) -> P:
    assert _RULES is not None
    return P(*[_RULES.get(a) if a is not None else None for a in axes])


def constrain(x, *axes: Optional[str]):
    """Annotate ``x`` with logical axes (one per dim; None = replicated)."""
    if _RULES is None:
        return x
    spec = logical_to_spec(axes)
    if _MESH is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(_MESH, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def active() -> bool:
    return _RULES is not None


def rule(name: str):
    """Mesh axes mapped to a logical axis (None outside a rules context)."""
    return _RULES.get(name) if _RULES is not None else None


def mesh():
    return _MESH
