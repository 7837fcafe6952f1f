"""Sharding rules: param / activation / cache PartitionSpecs with fallbacks.

The mesh is ("data", "model") (optionally a leading "pod" axis).  "model" is
the intra-pod H-tree analogue — tensor-parallel reductions stay on it; the
data axes carry only batch parallelism (PIMSAB's inter-tile rule: no
cross-tile partial-sum reduction).

Every rule has a *divisibility fallback*: a dimension that does not divide
the axis size replicates instead (recorded in ``MeshRules.decisions`` so the
dry-run can report what the planner actually did).  All emitted specs are
full-rank (one entry per dim) so tests can assert them structurally.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclass
class MeshRules:
    """Mesh + axis roles + the decision log of the sharding planner.

    ``mesh`` only needs ``.shape`` (axis → size dict) and ``.axis_names``;
    tests drive these rules with lightweight fakes.
    """

    mesh: Any
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    decisions: List[str] = field(default_factory=list)

    @classmethod
    def from_mesh(cls, mesh) -> "MeshRules":
        """All non-"model" axes are data-parallel (e.g. ("pod", "data"))."""
        dp = tuple(a for a in mesh.axis_names if a != "model")
        return cls(mesh=mesh, dp_axes=dp)

    # -- axis sizes --
    @property
    def dp(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def tp(self) -> int:
        return self.mesh.shape.get(self.tp_axis, 1) if self.tp_axis in self.mesh.axis_names else 1

    # -- decisions --
    def note(self, msg: str) -> None:
        if msg not in self.decisions:
            self.decisions.append(msg)

    def batch_axes(self, batch: int) -> Optional[Tuple[str, ...]]:
        """Data axes for a batch dim, or None (replicate) when it can't divide."""
        if batch % self.dp == 0 and batch >= self.dp:
            return self.dp_axes
        self.note(f"batch={batch} replicated: not divisible by dp={self.dp}")
        return None

    def tp_if(self, size: int, what: str) -> Optional[str]:
        """"model" if ``size`` divides the TP axis cleanly, else None."""
        if self.tp > 1 and size % self.tp == 0:
            return self.tp_axis
        self.note(f"{what}={size} replicated: not divisible by tp={self.tp}")
        return None


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _rep(ndim: int) -> P:
    return P(*([None] * ndim))


def _tp_both(rules: MeshRules, semantic: int, dim: int, what: str) -> Optional[str]:
    """Shard only when the *semantic* count (heads/experts/d_ff) AND the
    actual tensor dim both divide tp — mixer blocks reuse linear-layer key
    names (w_up/w_down) at other widths, and an indivisible dim would fail
    to lower."""
    ax = rules.tp_if(semantic, what)
    if ax is not None and dim % rules.tp != 0:
        rules.note(f"{what}: dim={dim} !% tp={rules.tp}, replicated")
        return None
    return ax


def _matmul_leaf_spec(path: Tuple[str, ...], shape, cfg, rules: MeshRules) -> P:
    """Spec of one linear-layer weight leaf (``w`` or ``w_q``).

    Stacked block leaves carry a leading scan-group axis which never shards;
    the matmul dims follow the Megatron pattern: column-parallel in
    (wq/wk/wv, latent attention's wq_b/wkv_b, w_gate/w_up, embed),
    row-parallel out (wo, w_down), experts on the TP axis for MoE; the
    latent projections (wq_a, wkv_a) and the router replicate.
    """
    grouped = path[0] in ("blocks", "dense_blocks", "enc_blocks")
    ndim = len(shape)
    # {"w": ...} leaf-dicts name the layer one level up; raw leaves name it
    # directly
    owner = path[-1]
    if owner in ("w", "w_q") and len(path) >= 2:
        owner = path[-2]

    def spec(*inner):
        inner = list(inner) + [None] * ((ndim - (1 if grouped else 0)) - len(inner))
        return P(*((None,) if grouped else ()), *inner)

    if owner == "embed":
        return P(_tp_both(rules, cfg.padded_vocab(), shape[0], "vocab"), None)
    if owner == "lm_head":
        return P(None, _tp_both(rules, cfg.padded_vocab(), shape[-1], "vocab"))
    if owner in ("wq", "wq_b", "wkv_b"):  # latent attention: heads out of the latents
        return spec(None, _tp_both(rules, cfg.n_heads, shape[-1], "q_heads"))
    if owner in ("wk", "wv"):
        return spec(None, _tp_both(rules, cfg.n_kv_heads, shape[-1], "kv_heads"))
    if owner == "wo":
        return spec(_tp_both(rules, cfg.n_heads, shape[-2], "q_heads"), None)
    if owner in ("w_gate", "w_up", "w_down") and ndim - (1 if grouped else 0) == 3:
        # MoE: (E, d_in, d_out) → shard the experts held here
        return spec(_tp_both(rules, cfg.n_held_experts, shape[-3], "experts"), None, None)
    if owner in ("w_gate", "w_up"):
        return spec(None, _tp_both(rules, cfg.d_ff, shape[-1], "d_ff"))
    if owner == "w_down":
        return spec(_tp_both(rules, cfg.d_ff, shape[-2], "d_ff"), None)
    return _rep(ndim)


def param_specs(shapes: Any, cfg, rules: MeshRules) -> Any:
    """PartitionSpec tree mirroring a param tree (arrays or SDS leaves).

    Linear leaf-dicts ({"w"| "w_q", ["w_scale"], ["b"]}) shard together:
    scale/bias follow the weight's output-dim entry.  Everything unrecognized
    (norm scales, recurrent mixers, adapters) replicates — safe on any mesh.
    """

    def visit(path: Tuple[str, ...], node) -> Any:
        if not isinstance(node, dict):
            return _matmul_leaf_spec(path, node.shape, cfg, rules)
        wkey = "w" if "w" in node else ("w_q" if "w_q" in node else None)
        if wkey is not None and hasattr(node[wkey], "shape"):
            wspec = _matmul_leaf_spec(path + (wkey,), node[wkey].shape, cfg, rules)
            out = {wkey: wspec}
            out_axis = tuple(wspec)[-1] if len(tuple(wspec)) else None
            for extra in ("w_scale", "b"):
                if extra in node:
                    nd = len(node[extra].shape)
                    out[extra] = P(*([None] * (nd - 1)), out_axis)
            for k, v in node.items():
                if k not in out:
                    out[k] = visit(path + (k,), v)
            return out
        return {k: visit(path + (k,), v) for k, v in node.items()}

    return visit((), shapes)


# ---------------------------------------------------------------------------
# activation / cache specs
# ---------------------------------------------------------------------------


def act_spec(batch: int, rules: MeshRules) -> P:
    """(B, S, D) activations: batch over the data axes, rest replicated."""
    return P(rules.batch_axes(batch), None, None)


def constrain(x, rules: Optional[MeshRules], spec: Optional[P]):
    """``with_sharding_constraint`` when a real mesh is active, else identity."""
    if rules is None or spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def cache_entry_spec(
    shape: Tuple[int, ...], cfg, rules: MeshRules, *, seq_shard_kv: bool = False
) -> P:
    """Spec for one decode-cache entry leaf (group axis already stripped).

    KV layout (B, T, H, hd) (+ (B, T, H) scales): heads shard on "model"
    when kv-heads divide tp; otherwise, with ``seq_shard_kv``, the sequence
    dim shards instead (ring-attention-style distributed decode); otherwise
    replicate everything but batch.  Recurrent states (B, W): batch only.
    """
    ndim = len(shape)
    parts: List[Any] = [None] * ndim
    if ndim >= 1:
        parts[0] = rules.batch_axes(shape[0])
    if ndim >= 3:
        # dim 2 is the kv-head axis on 4D kv and 3D scale entries
        if rules.tp > 1 and cfg.n_kv_heads % rules.tp == 0 and shape[2] == cfg.n_kv_heads:
            parts[2] = rules.tp_axis
        elif seq_shard_kv and rules.tp > 1 and shape[1] % rules.tp == 0:
            parts[1] = rules.tp_axis
            rules.note(
                f"kv_heads={cfg.n_kv_heads} !% tp={rules.tp}: sequence-sharded KV cache"
            )
    return P(*parts)
