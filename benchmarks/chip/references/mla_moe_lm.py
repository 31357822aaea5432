"""Plain reference of a latent-attention MoE decoder LM (the DeepSeek-V3
block: Moonlight-16B-A3B), with one chip's share of the routed experts.

Straight ``jax.numpy`` in float32, every matmul at ``HIGHEST`` precision,
no cache, no absorption, no sorting, no kernels.  It follows the published
block (DeepSeek-V3, arXiv:2412.19437; MLA, DeepSeek-V2, arXiv:2405.04434)::

    a          = rms(x)
    q          = Wq a                    per head: q_nope | q_pe
    c | k_pe   = Wkv_a a;  c = rms(c)    the latent and one shared key head
    k_nope | v = Wkv_b c                 per head, for every position
    h          = x + Wo . attn([q_nope, RoPE(q_pe)], [k_nope, RoPE(k_pe)], v)
    y          = h + shared(rms(h)) + sum_e g_e expert_e(rms(h))

Attention is causal with scale ``1 / sqrt(nope + rope)``; RoPE rotates
consecutive pairs ``(2i, 2i + 1)`` (DeepSeek-V3's ``rope_interleave``).
The first ``n_dense_layers`` layers have a SwiGLU of ``d_ff`` in place of
the experts.  Routing: scores ``s = sigmoid(W_r rms(h))`` over all
``router_experts``; the top ``top_k`` of ``s + bias`` are chosen (the bias
chooses, it never weighs); ``g`` is ``s`` at the chosen experts,
renormalised over them and times ``routed_scale``.  Of the chosen experts
only those held here (``n_experts`` from ``first_expert``) are computed,
each on every token with its weight (zero where the token did not choose
it); what the experts held elsewhere would add is left out, as the program
leaves it out.  Then a final RMSNorm and the untied LM head.

It imports nothing of the program.  Its weights come from
:func:`init_params`, which the benchmark also hands to the program, in the
program's parameter tree.  Departures from a float32 reference:

* the weights are stored in bfloat16, what the program is handed, and are
  upcast to float32 one layer at a time: float32 storage of one chip's
  share (13.5 GB) would not fit beside the activations;
* the comparisons run in blocks of :data:`ROWS` rows, so that a check
  fits on the chip once the program's state is freed.

``mm`` selects the arithmetic of every matmul: ``exact`` (the reference)
or ``fp8`` (the control, one precision step below the bfloat16 the
configuration computes in), as in ``dense_lm``.

Top-k routing is not continuous: where two experts' choice scores nearly
tie, any rounding may pick the other, and one other expert moves that
position's logits far more than rounding does.  With random weights such
ties are common (26 expert layers, 6 of 64: most positions lie within
two bfloat16 spacings of one at some layer), and a tipped tie moves the
logits of later positions too, through attention.  So the widest gap
over a flow reads the ties, and bfloat16 and fp8 read alike.  The
comparisons read two numbers of a flow's gaps instead, and the larger
is compared: their :data:`QUANTILE`, which lower precision lifts across
the board while a bfloat16 program's tipped ties touch a few percent of
positions; and their widest less :data:`TIE_ALLOWANCE`, which only a
token that no tie explains reaches.  They log how many positions lie
near a tie, by expert layer.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.references.dense_lm import exact, fp8, rms, root_key

#: rows of a batch compared at a time
ROWS = 4
#: the latent's RMSNorm epsilon: the published code's class default for
#: ``kv_a_layernorm``, not the model's ``rms_norm_eps``
KV_NORM_EPS = 1e-6
#: standard deviation of the routers' correction bias (drawn from the seed:
#: the published model does not give it)
BIAS_STD = 0.01
#: bits of bfloat16's significand after the leading one
BF16_BITS = 7
#: a routing whose k-th and next choice scores lie closer than this many
#: bfloat16 spacings (at the k-th score), where either expert is held
#: here, is logged as near a tie that bfloat16 arithmetic may break
TIE_SPACINGS = 2.0
#: the quantile of a flow's gaps that is read: above the share of
#: positions that a bfloat16 program's tipped ties move, below the share
#: that a lower precision moves
QUANTILE = 0.9
#: how far below the reference's best a tipped routing tie can move a
#: served token; the widest gap is read less this, so that only a token
#: no tie explains (a wrong one) reads there
TIE_ALLOWANCE = 2.5


# ------------------------------------------------------------------ weights

def param_shapes(cfg: dict) -> dict:
    """The parameter tree: ``{path: (shape, std or None for ones)}``; a
    weight's standard deviation is ``1 / sqrt(fan_in)``."""
    d, h, vocab = cfg["d_model"], cfg["n_heads"], cfg["vocab_size"]
    m, e = cfg["mla"], cfg["moe"]
    r, nope, rope, vd = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])

    def w(shape, fan_in):
        return tuple(shape), fan_in ** -0.5

    def ones(shape):
        return tuple(shape), None

    def swiglu(n, width):
        return {"gate": {"w": w((n, d, width), d)},
                "up": {"w": w((n, d, width), d)},
                "down": {"w": w((n, width, d), width)}}

    def stack(n, dense):
        spec = {
            "attn_norm": {"scale": ones((n, d))},
            "attn": {"wq": {"w": w((n, d, h * (nope + rope)), d)},
                     "wkv_a": {"w": w((n, d, r + rope), d)},
                     "kv_norm": {"scale": ones((n, r))},
                     "wkv_b": {"w": w((n, r, h * (nope + vd)), r)},
                     "wo": {"w": w((n, h * vd, d), h * vd)}},
            "mlp_norm": {"scale": ones((n, d))},
        }
        if dense:
            spec["mlp"] = swiglu(n, cfg["d_ff"])
            return spec
        held, f = e["n_experts"], e["d_ff"]
        spec["moe"] = {
            "router": {"w": w((n, d, e["router_experts"]), d),
                       "bias": ((n, e["router_experts"]), BIAS_STD)},
            "experts": {"gate": w((n, held, d, f), d),
                        "up": w((n, held, d, f), d),
                        "down": w((n, held, f, d), f)},
        }
        if e.get("shared_d_ff"):
            spec["moe"]["shared"] = swiglu(n, e["shared_d_ff"])
        return spec

    shapes = {"embed": {"table": w((vocab, d), d)},
              "final_norm": {"scale": ones((d,))},
              "lm_head": {"w": w((d, vocab), d)}}
    for name, n, dense in stacks(cfg):
        shapes[name] = stack(n, dense)
    return shapes


def stacks(cfg: dict) -> list:
    """``(name, layers, dense)`` of each stack of layers, in order."""
    dense = cfg.get("n_dense_layers", 0)
    out = [("dense_blocks", dense, True)] if dense else []
    return out + [("blocks", cfg["n_layers"] - dense, False)]


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, shapes_items: tuple, dtype: str):
    out = []
    for i, (shape, std) in enumerate(shapes_items):
        if std is None:
            out.append(jnp.ones(shape, dtype))
            continue
        bound = 3.0 ** 0.5 * std       # uniform with standard deviation std
        out.append(jax.random.uniform(jax.random.fold_in(key, i), shape,
                                      jnp.dtype(dtype), -bound, bound))
    return out


def init_params(cfg: dict, seed: int, dtype: str | None = None,
                shardings=None):
    """The weights of seed ``seed``, made on the device in one jitted call,
    in the configuration's parameter dtype.  With ``shardings`` each device
    makes only its own shards; the numbers are the same."""
    leaves, treedef = jax.tree_util.tree_flatten(param_shapes(cfg),
                                                 is_leaf=_is_leaf)
    items_ = tuple((tuple(s), f) for s, f in leaves)
    init = _init if shardings is None else jax.jit(
        _init.__wrapped__, static_argnums=(1, 2),
        out_shardings=treedef.flatten_up_to(shardings))
    made = init(root_key(seed), items_, dtype or cfg["param_dtype"])
    return jax.tree_util.tree_unflatten(treedef, made)


# ------------------------------------------------------------------ forward

def rope_pairs(x, theta):
    """x: [B, S, H, D]; rotate pairs (2i, 2i + 1) by position."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1
                     ).reshape(x.shape)


def attention(cfg: dict, p: dict, a, mm):
    """Expanded latent attention of the normed input ``a`` [B, S, D]."""
    b, s, _ = a.shape
    m, h = cfg["mla"], cfg["n_heads"]
    nope, rope, r = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["kv_lora_rank"]
    q = mm(a, p["wq"]["w"], "bsd,de->bse").reshape(b, s, h, nope + rope)
    ckv = mm(a, p["wkv_a"]["w"], "bsd,de->bse")
    c = rms(ckv[..., :r], p["kv_norm"]["scale"], KV_NORM_EPS)
    k_pe = rope_pairs(ckv[..., None, r:], cfg["rope_theta"])
    kv = mm(c, p["wkv_b"]["w"], "bsc,ce->bse").reshape(b, s, h, -1)
    q = jnp.concatenate([q[..., :nope],
                         rope_pairs(q[..., nope:], cfg["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    scores = mm(q, k, "bqhd,bkhd->bhqk") / jnp.sqrt(jnp.float32(nope + rope))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm(probs, kv[..., nope:], "bhqk,bkhd->bqhd").reshape(b, s, -1)
    return mm(att, p["wo"]["w"], "bse,ed->bsd")


def swiglu(p: dict, x, mm):
    gate = mm(x, p["gate"]["w"], "bsd,df->bsf")
    up = mm(x, p["up"]["w"], "bsd,df->bsf")
    return mm(jax.nn.silu(gate) * up, p["down"]["w"], "bsf,fd->bsd")


def choose(cfg: dict, p: dict, x, mm):
    """Router indices [B, S, k] and combine weights [B, S, k]."""
    e = cfg["moe"]
    scores = jax.nn.sigmoid(mm(x, p["router"]["w"], "bsd,de->bse"))
    _, idx = jax.lax.top_k(scores + p["router"]["bias"], e["top_k"])
    g = jnp.take_along_axis(scores, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return idx, g * e["routed_scale"]


def experts(cfg: dict, p: dict, x, mm):
    """The held experts' part of the layer, plus the shared experts."""
    e = cfg["moe"]
    idx, g = choose(cfg, p, x, mm)
    held = e.get("first_expert", 0) + jnp.arange(e["n_experts"])
    # each held expert's weight for each token, zero where not chosen
    weight = jnp.sum(jnp.where(idx[..., None] == held, g[..., None], 0.0),
                     axis=-2)
    gate = mm(x, p["experts"]["gate"], "bsd,edf->bsef")
    up = mm(x, p["experts"]["up"], "bsd,edf->bsef")
    hidden = weight[..., None] * jax.nn.silu(gate) * up
    out = mm(hidden, p["experts"]["down"], "bsef,efd->bsd")
    if "shared" in p:
        out = out + swiglu(p["shared"], x, mm)
    return out


def tie_margin(cfg: dict, p: dict, x):
    """How far ``[B, S]`` each position's routing lies from a tie that
    would change the experts held here: the margin between the k-th and
    the next choice score, in units of bfloat16's spacing at the k-th
    score, where either of the two is held here (infinite where neither
    is: swapping them changes nothing on this chip)."""
    e = cfg["moe"]
    held = e["first_expert"] + jnp.arange(e["n_experts"])
    choice = (jax.nn.sigmoid(exact(x, p["router"]["w"], "bsd,de->bse"))
              + p["router"]["bias"])
    top, idx = jax.lax.top_k(choice, e["top_k"] + 1)
    kth, nxt = top[..., -2], top[..., -1]
    spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.abs(kth))) - BF16_BITS)
    at_edge = jnp.any(idx[..., -2:, None] == held, (-1, -2))
    return jnp.where(at_edge, (kth - nxt) / spacing, jnp.inf)


def layer(cfg: dict, p: dict, x, mm, dense: bool):
    """One layer; returns x and its routing's :func:`tie_margin` (infinite
    for a dense layer)."""
    eps = cfg["norm_eps"]
    x = x + attention(cfg, p["attn"], rms(x, p["attn_norm"]["scale"], eps),
                      mm)
    h = rms(x, p["mlp_norm"]["scale"], eps)
    if dense:
        return x + swiglu(p["mlp"], h, mm), jnp.full(h.shape[:2], jnp.inf)
    return x + experts(cfg, p["moe"], h, mm), tie_margin(cfg, p["moe"], h)


def forward(cfg: dict, params: dict, tokens, first: int, mm=exact):
    """Logits ``[B, S - first, V]`` of positions ``first ..`` of ``tokens``
    ``[B, S]``, layer by layer, each layer's weights upcast to float32 as
    it is reached; and each expert layer's :func:`tie_margin` at those
    positions ``[layers, B, S - first]``."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    margins = []
    for name, _, dense in stacks(cfg):
        def body(x, p, dense=dense):
            p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
            return layer(cfg, p, x, mm, dense)

        x, m = jax.lax.scan(body, x, params[name])
        if not dense:
            margins.append(m[:, :, first:])
    x = rms(x[:, first:], params["final_norm"]["scale"].astype(jnp.float32),
            cfg["norm_eps"])
    out = mm(x, params["lm_head"]["w"].astype(jnp.float32), "bsd,dv->bsv")
    return out, jnp.concatenate(margins)


def logits(cfg: dict, params: dict, tokens, first: int, mm=exact):
    """The logits of :func:`forward`."""
    return forward(cfg, params, tokens, first, mm)[0]


# ------------------------------------------------------------- comparisons

def _gap(ref, tokens):
    """How far below the reference's best logit each token's logit lies."""
    picked = jnp.take_along_axis(ref, tokens[..., None], -1)[..., 0]
    return jnp.max(ref, -1) - picked


@functools.partial(jax.jit, static_argnums=(0, 3, 5))
def _block(cfg_items: tuple, params, tokens, first: int, served,
           control: bool):
    """Gaps ``[B, T]`` of one block of rows, and the tie margins of their
    routings ``[layers, B, T]``."""
    cfg = config(cfg_items)
    ref, margins = forward(cfg, params, tokens, first)
    if control:
        served = jnp.argmax(logits(cfg, params, tokens, first, mm=fp8), -1)
    return _gap(ref, served), margins


def _by_rows(cfg_items: tuple, params, tokens, first: int, served,
             control: bool):
    """The flow's two readings: the :data:`QUANTILE` of its gaps, and its
    widest gap less :data:`TIE_ALLOWANCE`."""
    gaps, margins = [], []
    for lo in range(0, tokens.shape[0], ROWS):
        rows = slice(lo, lo + ROWS)
        gap, m = _block(cfg_items, params, tokens[rows], first,
                        None if control else served[rows], control)
        gaps.append(np.asarray(gap))
        margins.append(np.asarray(m))
    gaps, margins = np.concatenate(gaps), np.concatenate(margins, axis=1)
    near = margins < TIE_SPACINGS
    quantiles = np.quantile(gaps, [0.5, QUANTILE, 0.99, 1.0])
    print(f"routing ties: {int(np.any(near, axis=0).sum())} of {gaps.size} "
          f"positions within {TIE_SPACINGS} bf16 spacings of one, by expert "
          f"layer {near.sum(axis=(1, 2)).tolist()}; gaps at quantiles 0.5, "
          f"{QUANTILE}, 0.99, 1: {quantiles.tolist()}", file=sys.stderr)
    return jnp.asarray([quantiles[1], quantiles[-1] - TIE_ALLOWANCE])


def served_gaps(cfg_items: tuple, params, tokens, first: int, served):
    """The readings of the gaps of the served tokens ``served`` ``[B, T]``
    at positions ``first ..`` of ``tokens`` (the prompt and all but the
    last served token), against the reference: their :data:`QUANTILE`
    and their widest less :data:`TIE_ALLOWANCE`."""
    return _by_rows(cfg_items, params, tokens, first, served, False)


def control_gaps(cfg_items: tuple, params, tokens, first: int):
    """The same readings of the gaps of the tokens the fp8 control puts
    first, at the same positions."""
    return _by_rows(cfg_items, params, tokens, first, None, True)


def items(cfg: dict) -> tuple:
    """The configuration, its sections included, as a hashable static
    argument; :func:`config` reverses it."""
    return tuple(sorted((k, items(v) if isinstance(v, dict) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, dict))))


def config(cfg_items: tuple) -> dict:
    return {k: config(v) if isinstance(v, tuple) else v
            for k, v in cfg_items}
