"""Sharding plans: declarative parameter/state placement over a named mesh.

TPU-native replacement for the reference's multi-device graph builders
(``ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:39,594,677`` —
which clone ops per device and insert collectives per gradient) and the
DistributeTranspiler's param-block placement (``transpiler/
distribute_transpiler.py:494``). Here, placement is data, not graph surgery:
a :class:`ShardingPlan` maps parameter paths to ``PartitionSpec``s; pjit +
GSPMD then insert all collectives (the AllReduceOpHandle /
ReduceOpHandle / BroadcastOpHandle world) automatically.

Precedence for a parameter's spec:
  1. first matching plan rule (regex over the "/"-joined path)
  2. the ParamSpec.sharding hint declared by the layer
  3. replicated (P())

Axes of size 1 in the mesh are harmless in any spec, so plans are written
once and reused across mesh shapes (dp-only, dp x tp, fsdp, ...).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core import mesh as mesh_lib


@dataclasses.dataclass
class Rule:
    pattern: str           # regex matched against "/".join(path)
    spec: Optional[P]      # PartitionSpec (None = replicated)

    def __post_init__(self):
        self._re = re.compile(self.pattern)

    def matches(self, path_str: str) -> bool:
        return self._re.search(path_str) is not None


class ShardingPlan:
    """Ordered rules mapping param paths to PartitionSpecs.

    ``fsdp_largest_dim=True`` additionally shards the largest dim of any
    big parameter over the "fsdp" axis when no rule/hint names it (ZeRO-3
    analog — capability absent in the reference, SURVEY.md §2.6 last row).
    """

    def __init__(self, rules: Sequence[Tuple[str, Optional[P]]] = (),
                 *, fsdp_largest_dim: bool = False,
                 fsdp_min_size: int = 2 ** 16):
        self.rules = [Rule(p, s) for p, s in rules]
        self.fsdp_largest_dim = fsdp_largest_dim
        self.fsdp_min_size = fsdp_min_size

    def spec_for(self, path: Tuple[str, ...], hint: Optional[P],
                 shape: Tuple[int, ...] = ()) -> P:
        path_str = "/".join(path)
        for rule in self.rules:
            if rule.matches(path_str):
                return rule.spec if rule.spec is not None else P()
        spec = hint if hint is not None else P()
        if self.fsdp_largest_dim and shape and not _names_axis(spec, "fsdp"):
            size = 1
            for d in shape:
                size *= d
            if size >= self.fsdp_min_size:
                spec = _add_fsdp(spec, shape)
        return spec

    # -- tree builders ----------------------------------------------------
    def params_specs(self, params, hints=None) -> Any:
        """Pytree of PartitionSpecs matching ``params``.

        ``hints`` is an optional matching pytree of PartitionSpec-or-None
        (e.g. ``model.sharding_specs(params)``).
        """
        def walk(tree, hint_tree, path):
            if isinstance(tree, dict):
                return {
                    k: walk(v,
                            hint_tree.get(k) if isinstance(hint_tree, dict)
                            else None,
                            path + (k,))
                    for k, v in tree.items()
                }
            hint = hint_tree if isinstance(hint_tree, (P, type(None))) else None
            shape = getattr(tree, "shape", ())
            return self.spec_for(path, hint, tuple(shape))

        return walk(params, hints or {}, ())

    def state_specs(self, state, hints=None) -> Any:
        """Specs for a full train state {params, opt, step, ...}.

        Optimizer slot buffers inherit their parameter's spec (the reference
        keeps accumulators on the param's device for the same reason —
        ``optimizer.py`` accumulators live beside params). Scalars/steps are
        replicated.
        """
        pspecs = self.params_specs(state["params"], hints)
        out = {}
        for key, val in state.items():
            if key == "params":
                out[key] = pspecs
            elif key == "opt":
                out[key] = _opt_specs(val, pspecs)
            else:
                out[key] = jax.tree_util.tree_map(lambda _: P(), val)
        return out


def _opt_specs(opt_state, pspecs):
    if isinstance(opt_state, dict):
        out = {}
        for k, v in opt_state.items():
            if k == "slots" and isinstance(v, dict):
                out[k] = {name: pspecs for name in v}
            else:
                out[k] = jax.tree_util.tree_map(lambda _: P(), v)
        return out
    return jax.tree_util.tree_map(lambda _: P(), opt_state)


def _names_axis(spec: P, axis: str) -> bool:
    for entry in spec:
        if entry == axis:
            return True
        if isinstance(entry, tuple) and axis in entry:
            return True
    return False


def _add_fsdp(spec: P, shape: Tuple[int, ...]) -> P:
    """Shard the largest currently-unsharded dim over "fsdp"."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if entries[i] is None:
            entries[i] = "fsdp"
            break
        if isinstance(entries[i], str):
            entries[i] = (entries[i], "fsdp")
            break
    return P(*entries)


def named_shardings(mesh: Mesh, specs: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


# Canned plans --------------------------------------------------------------

def replicated_plan() -> ShardingPlan:
    """Pure data parallel: all params replicated; grads all-reduced by XLA.
    ≙ AllReduceSSAGraphBuilder (multi_devices_graph_pass.cc:594)."""
    return ShardingPlan()


def fsdp_plan(min_size: int = 2 ** 16) -> ShardingPlan:
    """ZeRO-3 style: big params sharded over "fsdp"."""
    return ShardingPlan(fsdp_largest_dim=True, fsdp_min_size=min_size)


def megatron_plan() -> ShardingPlan:
    """Honor per-layer TP hints (Linear declares Megatron col/row specs);
    everything else replicated."""
    return ShardingPlan()


def serving_tp_plan() -> ShardingPlan:
    """Specs for the serving engine's head-major tensor-parallel param
    layout (``ServingEngine(mesh=...)``, ISSUE 15): the fused qkv
    weight reshaped ``(D, 3, H, Dh)`` is column-sharded over "tp" on
    the HEAD axis and the output projection reshaped ``(H, Dh, D)`` is
    row-sharded — the canonical SpecLayout qkv-col / attn-out-row
    Megatron split (SNIPPETS.md), applied at head granularity because a
    raw ``(D, 3D)`` column shard would straddle the q/k/v boundaries.
    Everything else (embeddings, layer norms, MLP, logits) is
    replicated: decode is KV-bandwidth-bound, and keeping the MLP
    replicated is what holds the sharded step to ONE collective — the
    psum at each layer's attention output."""
    return ShardingPlan(rules=[
        (r"attn/qkv_tp/weight$", P(None, None, "tp", None)),
        (r"attn/qkv_tp/bias$", P(None, "tp", None)),
        (r"attn/out_tp/weight$", P("tp", None, None)),
        (r"^", P()),      # everything else replicated
    ])


def serving_prefill_tp_plan() -> ShardingPlan:
    """:func:`serving_tp_plan` plus the Megatron MLP split the PREFILL
    tier wants (ISSUE 19): prefill is flops-bound, so the MLP matmuls
    dominate and sharding them is worth a second collective per layer.
    ``fc1`` (the SpecLayout ``ffn_up``) is column-sharded over "tp" on
    its output dim, ``fc2`` (``down``) is row-sharded on its input dim,
    and the fc2 bias stays replicated so it is added exactly once AFTER
    the psum of the row-parallel partial products. Decode-tier and
    colocated engines keep :func:`serving_tp_plan`'s replicated MLP and
    its single-psum step shape."""
    return ShardingPlan(rules=[
        (r"attn/qkv_tp/weight$", P(None, None, "tp", None)),
        (r"attn/qkv_tp/bias$", P(None, "tp", None)),
        (r"attn/out_tp/weight$", P("tp", None, None)),
        (r"mlp/fc1/weight$", P(None, "tp")),
        (r"mlp/fc1/bias$", P("tp")),
        (r"mlp/fc2/weight$", P("tp", None)),
        (r"^", P()),      # everything else (incl. fc2 bias) replicated
    ])
