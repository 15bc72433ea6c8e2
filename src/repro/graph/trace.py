"""jaxpr -> op-graph tracer.

``trace(fn, *example_args)`` stages ``fn`` with :func:`jax.make_jaxpr` and
lowers the jaxpr to the :mod:`repro.graph.ir` vocabulary, one IR node per
primitive equation.  Two things make the result a *compiler* IR rather
than a jaxpr mirror:

* **call-like equations are inlined** — ``jit``, ``custom_jvp_call`` /
  ``custom_vjp_call`` (every ``jax.nn`` activation is a custom_jvp
  function) and remat wrappers are flattened into their body equations, so
  a ``jax.nn.relu`` shows up as the fusable ``max(x, 0)`` it is instead of
  an opaque call;
* **weights become graph consts** — anything ``fn`` closes over
  (``lambda x: forward(params, x)``) lands in ``Value(kind="const")``, so
  the passes can distinguish streamed weights from activations (quant
  folding keys on int8 consts).

Control flow: short ``scan`` equations (length <= ``SCAN_UNROLL_CAP``)
are **unrolled** — the body is evaluated once per iteration, carries are
threaded through, and the per-iteration outputs are re-stacked — so a
recurrent decode step written as a layer scan still exposes its matmuls
to the fusion passes.  Longer scans, ``while`` and ``cond`` stay opaque
single nodes the executor re-binds.  Model entry points meant for graph
compilation should still trace with ``scan_layers=False`` when they can
(the compiler does this for you; see
:func:`repro.graph.compiler.compile_prefill_step`) — unrolling at the
source beats unrolling in the tracer.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from jax.core import DropVar
from jax.extend.core import ClosedJaxpr, Literal

from .ir import Graph, Node, Value, canonical_op

#: Call-like primitives that are pure wrappers around a body jaxpr: the
#: tracer flattens them.  param key -> how to find the body.
_INLINE_CALLS = ("jit", "custom_jvp_call", "custom_vjp_call", "remat2",
                 "closed_call")
_BODY_PARAM_KEYS = ("jaxpr", "call_jaxpr")

#: Longest ``lax.scan`` the tracer unrolls into the graph.  A deep layer
#: scan past this produces a graph too large to fuse profitably (and to
#: compile node-by-node), so it stays an opaque node instead.
SCAN_UNROLL_CAP = 64


def _scan_unrolled_body(eqn) -> Any:
    """A ClosedJaxpr equivalent to a ``scan`` equation with the loop
    unrolled: ``length`` sequential body evaluations, carries threaded
    through, per-iteration outputs re-stacked along axis 0.  ``None`` when
    the scan is too long (or zero-length) to unroll."""
    p = eqn.params
    length, n_consts, n_carry = p["length"], p["num_consts"], p["num_carry"]
    if not 0 < length <= SCAN_UNROLL_CAP:
        return None
    body = p["jaxpr"]  # ClosedJaxpr

    def unrolled(*flat):
        consts = flat[:n_consts]
        carry = list(flat[n_consts:n_consts + n_carry])
        xs = flat[n_consts + n_carry:]
        ys = []
        order = range(length - 1, -1, -1) if p["reverse"] else range(length)
        for i in order:
            outs = jax.core.eval_jaxpr(
                body.jaxpr, body.consts, *consts, *carry,
                *[x[i] for x in xs])
            carry = list(outs[:n_carry])
            ys.append(outs[n_carry:])
        if p["reverse"]:
            ys.reverse()  # ys are stacked in xs index order either way
        stacked = [jnp.stack(col) for col in zip(*ys)]
        return (*carry, *stacked)

    examples = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                for v in eqn.invars]
    return jax.make_jaxpr(unrolled)(*examples)


def _closed_body(eqn) -> Any:
    """The body ClosedJaxpr of a call-like equation, or None."""
    if eqn.primitive.name not in _INLINE_CALLS:
        return None
    for key in _BODY_PARAM_KEYS:
        body = eqn.params.get(key)
        if body is None:
            continue
        if hasattr(body, "jaxpr"):          # already a ClosedJaxpr
            return body
        return ClosedJaxpr(body, ())  # open Jaxpr (remat2)
    return None


def trace(fn: Callable, *example_args, name: str = "graph") -> Graph:
    """Lower ``fn(*example_args)`` to a :class:`~repro.graph.ir.Graph`.

    ``example_args`` may be concrete arrays or ``jax.ShapeDtypeStruct``
    pytrees — only shapes/dtypes matter.  Values ``fn`` closes over become
    graph consts; the graph's ``in_tree``/``out_tree`` record the pytree
    signature so the executor can be called exactly like ``fn``.
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    out_tree = jax.tree_util.tree_structure(
        jax.eval_shape(fn, *example_args))
    flat_args, in_tree = jax.tree_util.tree_flatten(example_args)

    g = Graph(values={}, nodes=[], inputs=[], outputs=[],
              in_tree=in_tree, out_tree=out_tree, name=name)
    env: Dict[Any, int] = {}  # jaxpr Var -> value id

    jaxpr = closed.jaxpr
    assert len(jaxpr.invars) == len(flat_args), \
        (len(jaxpr.invars), len(flat_args))
    for var in jaxpr.invars:
        v = g.new_value(var.aval.shape, var.aval.dtype, kind="input")
        env[var] = v.id
        g.inputs.append(v.id)
    _bind_consts(g, env, jaxpr.constvars, closed.consts)
    _lower_eqns(g, env, jaxpr.eqns)
    for var in jaxpr.outvars:
        g.outputs.append(_read(g, env, var))
    return g


def _bind_consts(g: Graph, env, constvars, consts) -> None:
    for var, const in zip(constvars, consts):
        v = g.new_value(var.aval.shape, var.aval.dtype, kind="const",
                        array=jnp.asarray(const))
        env[var] = v.id


def _read(g: Graph, env, var) -> int:
    if isinstance(var, Literal):
        v = g.new_value(var.aval.shape, var.aval.dtype, kind="const",
                        array=jnp.asarray(var.val, var.aval.dtype))
        return v.id
    return env[var]


def _lower_eqns(g: Graph, env, eqns) -> None:
    for eqn in eqns:
        body = _closed_body(eqn)
        if body is None and eqn.primitive.name == "scan":
            body = _scan_unrolled_body(eqn)
        if body is not None:
            # Inline: wire the call's operands to the body's invars, lower
            # the body equations into the same graph, then alias the
            # call's outvars to the body's outvars.
            sub_env: Dict[Any, int] = {}
            assert len(body.jaxpr.invars) == len(eqn.invars), eqn.primitive
            for ivar, ovar in zip(body.jaxpr.invars, eqn.invars):
                sub_env[ivar] = _read(g, env, ovar)
            _bind_consts(g, sub_env, body.jaxpr.constvars, body.consts)
            _lower_eqns(g, sub_env, body.jaxpr.eqns)
            for call_out, body_out in zip(eqn.outvars, body.jaxpr.outvars):
                if not isinstance(call_out, DropVar):
                    env[call_out] = _read(g, sub_env, body_out)
            continue

        in_ids = tuple(_read(g, env, v) for v in eqn.invars)
        out_ids = []
        for ovar in eqn.outvars:
            v = g.new_value(ovar.aval.shape, ovar.aval.dtype)
            out_ids.append(v.id)
            if not isinstance(ovar, DropVar):
                env[ovar] = v.id
        g.nodes.append(Node(
            id=g.next_node_id(),
            op=canonical_op(eqn.primitive.name),
            inputs=in_ids,
            outputs=tuple(out_ids),
            attrs=dict(eqn.params),
            prim=eqn.primitive,
        ))


def eval_node(node: Node, invals) -> tuple:
    """Re-bind one primitive node on concrete (or traced) arguments.

    This is :func:`jax.core.eval_jaxpr`'s inner loop applied to a single
    equation; fused clusters eval their ``body`` nodes through it inside a
    single jit region.
    """
    assert node.prim is not None, "eval_node on a synthetic node"
    subfuns, bind_params = node.prim.get_bind_params(dict(node.attrs))
    out = node.prim.bind(*subfuns, *invals, **bind_params)
    return tuple(out) if node.prim.multiple_results else (out,)
