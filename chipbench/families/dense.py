"""Dense decoder with grouped-query attention (chatglm3-6b).

Per layer: RMSNorm, attention with ``num_kv_heads`` shared key/value heads
and rotary embedding over the first ``rope_fraction`` of each head, a
residual add, RMSNorm, a SwiGLU MLP and a residual add; then a final
RMSNorm and an untied output projection.  Norm weights are stored as an
offset from 1.  The parameter names and layouts are those the program's
dense family builds, with every layer's leaves stacked on axis 0.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from .. import reference as R
from ..weights import Rule, pow2

SQRT3 = math.sqrt(3.0)
#: norm weights are 1 + k/128 for k in [-32, 32): exact in bf16 after the +1
NORM: Rule = (0.0, 0.25, 6)
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


def padded_vocab(sizes: dict) -> int:
    return -(-sizes["vocab_size"] // 256) * 256


def head_dim(sizes: dict) -> int:
    return sizes.get("head_dim") or sizes["d_model"] // sizes["num_heads"]


def param_specs(sizes: dict) -> Dict[str, tuple]:
    L, d, ff = sizes["num_layers"], sizes["d_model"], sizes["d_ff"]
    h, hkv, dh = sizes["num_heads"], sizes["num_kv_heads"], head_dim(sizes)
    v = padded_vocab(sizes)
    return {
        "embed": (v, d),
        "blk.0.attn.wq": (L, d, h * dh), "blk.0.attn.wk": (L, d, hkv * dh),
        "blk.0.attn.wv": (L, d, hkv * dh), "blk.0.attn.wo": (L, h * dh, d),
        "blk.0.ln1": (L, d), "blk.0.ln2": (L, d),
        "blk.0.mlp.w_gate": (L, d, ff), "blk.0.mlp.w_up": (L, d, ff),
        "blk.0.mlp.w_down": (L, ff, d),
        "final_norm": (d,), "lm_head": (d, v),
    }


def stacked(name: str) -> bool:
    return name.startswith("blk.")


def rule(name: str, shape: tuple) -> Rule:
    """Input projections at unit gain, residual outputs at a quarter (so
    the residual stream is not swamped and rounding is not amplified
    through depth), logits with a spread of about 2.5."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("ln1", "ln2", "final_norm"):
        return NORM
    if name == "embed":
        return (0.0, 1.0, 16)
    fan_in = shape[-2]
    if name == "lm_head":
        return (0.0, pow2(SQRT3 * 2.5 / math.sqrt(fan_in)), 16)
    if leaf in ("wo", "w_down"):
        return (0.0, pow2(SQRT3 * 0.25 / math.sqrt(fan_in)), 16)
    if leaf in ("wq", "wk", "wv", "w_gate", "w_up"):
        return (0.0, pow2(SQRT3 / math.sqrt(fan_in)), 16)
    raise KeyError(f"no weight rule for {name!r}")


def layers(sizes: dict) -> List[Tuple[str, dict]]:
    out = []
    for i in range(sizes["num_layers"]):
        roles = {k: (f"blk.0.attn.{k}", i) for k in ATTN}
        roles.update({k: (f"blk.0.mlp.{k}", i) for k in MLP})
        roles.update({"ln1": ("blk.0.ln1", i), "ln2": ("blk.0.ln2", i)})
        out.append(("block", roles))
    return out


def attention_block(x, w, sizes, quant):
    """Pre-normed attention sub-layer (residual not added)."""
    b, t, _ = x.shape
    h, hkv, dh = sizes["num_heads"], sizes["num_kv_heads"], head_dim(sizes)
    q = R.mm(x, w["wq"], quant).reshape(b, t, h, dh)
    k = R.mm(x, w["wk"], quant).reshape(b, t, hkv, dh)
    v = R.mm(x, w["wv"], quant).reshape(b, t, hkv, dh)
    frac = sizes.get("rope_fraction", 1.0)
    q = R.rope(q, sizes["rope_theta"], frac)
    k = R.rope(k, sizes["rope_theta"], frac)
    return R.mm(R.causal_attention(q, k, v), w["wo"], quant)


def block(x, w, sizes, quant):
    import jax

    eps = sizes["norm_eps"]
    x = x + attention_block(R.rms_norm(x, 1.0 + w["ln1"], eps), w, sizes, quant)
    hn = R.rms_norm(x, 1.0 + w["ln2"], eps)
    g = jax.nn.silu(R.mm(hn, w["w_gate"], quant)) * R.mm(hn, w["w_up"], quant)
    return x + R.mm(g, w["w_down"], quant)


KINDS = {"block": block}


# ---------------------------------------------------------------------------
# Necessary work of one engine call, from shapes.
# ---------------------------------------------------------------------------

def _attn_span(rows) -> int:
    """Sum over new tokens of the keys each attends to (causal)."""
    return sum(n * ctx + n * (n + 1) // 2 for ctx, n in rows)


def call_cost(sizes: dict, rows, emit_rows: int) -> Tuple[float, float]:
    """``(flops, bytes)`` the algorithm needs for one call over ``rows``
    ``[(tokens already cached, new tokens)]``, ``emit_rows`` of which are
    projected to logits.  Bytes: every weight once, the cached keys and
    values of live tokens read, the new ones written, the embedding rows
    gathered and the logits written; never the padded table width."""
    L, d, ff = sizes["num_layers"], sizes["d_model"], sizes["d_ff"]
    h, hkv, dh = sizes["num_heads"], sizes["num_kv_heads"], head_dim(sizes)
    v = sizes["vocab_size"]
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff
    new = sum(n for _, n in rows)
    flops = (2.0 * L * per_layer * new + 2.0 * d * v * emit_rows
             + 4.0 * L * h * dh * _attn_span(rows))
    weight_bytes = 2.0 * (L * (per_layer + 2 * d) + d + d * padded_vocab(sizes))
    kv_bytes = 2.0 * 2 * L * hkv * dh * sum(ctx + n for ctx, n in rows)
    nbytes = weight_bytes + kv_bytes + 2.0 * d * new + 2.0 * v * emit_rows
    return flops, nbytes
