"""Shard-local MoE dispatch (EXPERIMENTS.md §Perf A5): the structural fix.

The sort-based dispatch in moe.py permutes tokens with data-dependent
indices; GSPMD cannot prove locality, so it replicates the (T, D) token
buffers across the data axis (the dominant memory term of the llama4 train
cell, immune to sharding constraints -- iteration A4).

Here the dispatch runs under ``shard_map`` (via :mod:`repro.dist.shmap`),
manual over the data axes with the model axis AUTO: every data shard
sorts and buckets ONLY its local tokens into a
local capacity buffer (E, C_local, D), computes its experts, and combines
locally.  Token
buffers never cross data shards; the only cross-shard traffic is the
explicit FSDP all-gather of the expert weights' d_ff slices -- exactly what
GSPMD's FSDP inserts for the dense layers anyway.

Scope: the expert-parallel layout (E divisible by the model axis, llama4).
Archs on the TP-inside-experts fallback (mixtral) keep the global path.
Trade-off vs the global dispatch: capacity is per-shard, so overflow drops
tokens per shard rather than globally -- standard GShard 'local group'
semantics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.annotate import current_mesh

from .moe import _moe_ffn_chunk

__all__ = ["moe_ffn_local"]

# manual(data)-axis view of the per-layer expert weight shardings
# (dist.sharding.lm_param_spec EP branch, minus the leading stacked dim,
# minus the auto model axis):
_WSPEC = (None, None, "data")    # wg/wu (E, D, F): F is the FSDP dim
_WDSPEC = (None, "data", None)   # wd (E, F, D)
_SSPEC = ("data", None)          # shared wg/wu (D, F*): D is the FSDP dim
_SDSPEC = (None, "data")         # shared wd (F*, D)


def _gather_leaf(leaf, spec, data_axes):
    # gather in f32: the BACKWARD of a bf16 all_gather is a bf16 psum, which
    # crashes XLA-CPU's AllReducePromotion pass (minimal repro in
    # EXPERIMENTS.md A5).  Costs 2x on gather bytes in this measurement;
    # on a real TPU backend the bf16 gather works and halves the traffic.
    out = leaf.astype(jnp.float32) if leaf.dtype == jnp.bfloat16 else leaf
    for dim, names in enumerate(spec):
        if names is None:
            continue
        for name in (names if isinstance(names, tuple) else (names,)):
            if name in data_axes:
                out = jax.lax.all_gather(out, name, axis=dim, tiled=True)
    return out.astype(leaf.dtype)


def moe_ffn_local(p, x, top_k, capacity_factor=1.25, act="silu",
                  token_chunk: int = 0):
    """Drop-in for moe_ffn with shard-local dispatch.  Falls back to the
    global path when no mesh is installed (unit tests, single host)."""
    mesh = current_mesh()
    if mesh is None:
        return _moe_ffn_chunk(p, x, top_k, capacity_factor, act)

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local(p_loc, x_loc):
        pw = {
            "router": p_loc["router"],
            "wg": _gather_leaf(p_loc["wg"], _WSPEC, data_axes),
            "wu": _gather_leaf(p_loc["wu"], _WSPEC, data_axes),
            "wd": _gather_leaf(p_loc["wd"], _WDSPEC, data_axes),
        }
        if "shared" in p_loc:
            pw["shared"] = {
                "wg": _gather_leaf(p_loc["shared"]["wg"], _SSPEC, data_axes),
                "wu": _gather_leaf(p_loc["shared"]["wu"], _SSPEC, data_axes),
                "wd": _gather_leaf(p_loc["shared"]["wd"], _SDSPEC, data_axes),
            }
        # full-f32 region: ANY bf16 collective (fwd or transposed bwd) in a
        # manual region crashes XLA-CPU's AllReducePromotion; f32 is the
        # measurable-on-CPU configuration (bytes 2x pessimistic, noted).
        xdt = x_loc.dtype
        pw = jax.tree.map(lambda t: t.astype(jnp.float32), pw)
        y, aux = _moe_ffn_chunk(pw, x_loc.astype(jnp.float32), top_k,
                                capacity_factor, act, annotate=False)
        y = y.astype(xdt)
        # NB: no pmean here -- a scalar all-reduce inside this manual region
        # trips XLA-CPU's AllReducePromotion pass (hard crash); per-shard aux
        # values are averaged outside instead.
        return y, aux[None]

    in_specs = (
        {
            "router": P(),
            "wg": P(*_WSPEC), "wu": P(*_WSPEC), "wd": P(*_WDSPEC),
            **({"shared": {"wg": P(*_SSPEC), "wu": P(*_SSPEC),
                           "wd": P(*_SDSPEC)}} if "shared" in p else {}),
        },
        P(data_axes, None),
    )
    from repro.dist.shmap import shard_map

    fn = shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=(P(data_axes, None), P(data_axes)),
        manual_axes=frozenset(data_axes), check=False,
    )
    y, aux_shards = fn({k: p[k] for k in in_specs[0]}, x)
    return y, aux_shards.mean()
