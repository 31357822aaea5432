"""Plain reference of a dense decoder LM (Llama-style; InternLM2, Phi-3).

Straight ``jax.numpy`` in float32, every matmul at ``HIGHEST`` precision,
no cache, no batching tricks, no kernels.  It follows the published block:

    h = x + Wo . attn(RoPE(Wq . rms(x)), RoPE(Wk . rms(x)), Wv . rms(x))
    y = h + Wdown . (silu(Wgate . rms(h)) * (Wup . rms(h)))

with RMSNorm (eps from the configuration, a learned scale), rotary
embeddings on the two halves of each head (theta from the configuration),
grouped-query attention (query head ``i`` reads key/value head
``i // (n_heads / n_kv_heads)``), a causal mask, a final RMSNorm and an
untied LM head.

It imports nothing of the program.  Its weights come from
:func:`init_params`, which the benchmark also hands to the program, so both
start from the same numbers made from the seed; the parameter tree uses
the layout the program's serving and training entry points take.

``mm`` selects the arithmetic of every matmul: :func:`exact` (the
reference) or :func:`fp8` (the control: each operand scaled by its
largest magnitude and rounded to float8 e4m3, one precision step below the
bfloat16 the configuration computes in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def exact(a, b, spec: str):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _round_fp8(x):
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                                  / 448.0)
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # the forward value is rounded; the derivative passes straight through
    return x + jax.lax.stop_gradient(rounded - x)


def fp8(a, b, spec: str):
    return exact(_round_fp8(a), _round_fp8(b), spec)


# ------------------------------------------------------------------ weights

def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def param_shapes(cfg: dict) -> dict:
    """The parameter tree: ``{path: (shape, fan_in or None for ones)}``."""
    d, hd, n = cfg["d_model"], head_dim(cfg), cfg["n_layers"]
    q, kv, f, v = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd, cfg["d_ff"], \
        cfg["vocab_size"]
    return {
        "embed": {"table": ((v, d), d)},
        "final_norm": {"scale": ((d,), None)},
        "lm_head": {"w": ((d, v), d)},
        "blocks": {
            "attn_norm": {"scale": ((n, d), None)},
            "mlp_norm": {"scale": ((n, d), None)},
            "attn": {"wq": {"w": ((n, d, q), d)}, "wk": {"w": ((n, d, kv), d)},
                     "wv": {"w": ((n, d, kv), d)}, "wo": {"w": ((n, q, d), q)}},
            "mlp": {"gate": {"w": ((n, d, f), d)}, "up": {"w": ((n, d, f), d)},
                    "down": {"w": ((n, f, d), f)}},
        },
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def root_key(seed: int):
    """A PRNG key from any whole number, however large."""
    seed %= 2**64
    key = jax.random.key(seed % 2**32, impl="rbg")
    return jax.random.fold_in(key, seed // 2**32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, shapes_items: tuple, dtype: str):
    out = []
    for i, (shape, fan_in) in enumerate(shapes_items):
        if fan_in is None:
            out.append(jnp.ones(shape, dtype))
            continue
        # uniform with standard deviation 1/sqrt(fan_in)
        bound = (3.0 / fan_in) ** 0.5
        out.append(jax.random.uniform(jax.random.fold_in(key, i), shape,
                                      jnp.dtype(dtype), -bound, bound))
    return out


def init_params(cfg: dict, seed: int, dtype: str | None = None,
                shardings=None):
    """The weights of seed ``seed``, made on the device in one jitted call,
    in the configuration's parameter dtype.  With ``shardings`` (a tree of
    the parameters' shape, one sharding a leaf) each device makes only its
    own shards; the numbers are the same."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_leaf)
    items = tuple((tuple(s), f) for s, f in leaves)
    init = _init if shardings is None else jax.jit(
        _init.__wrapped__, static_argnums=(1, 2),
        out_shardings=treedef.flatten_up_to(shardings))
    made = init(root_key(seed), items, dtype or cfg["param_dtype"])
    return jax.tree_util.tree_unflatten(treedef, made)


def spread(cfg: dict, mesh) -> dict:
    """A sharding of each parameter over every device of ``mesh``: split
    along its largest axis that the device count divides (the first of
    equals), whole where none does.  Placement only; XLA partitions the
    same arithmetic over it."""
    from jax.sharding import NamedSharding, PartitionSpec

    n = mesh.devices.size

    def leaf(spec):
        shape = spec[0]
        fits = [i for i, d in enumerate(shape) if d % n == 0]
        if not fits:
            return NamedSharding(mesh, PartitionSpec())
        axis = max(fits, key=lambda i: (shape[i], -i))
        parts = [None] * len(shape)
        parts[axis] = tuple(mesh.axis_names)
        return NamedSharding(mesh, PartitionSpec(*parts))

    return jax.tree_util.tree_map(leaf, param_shapes(cfg), is_leaf=_is_leaf)


# ------------------------------------------------------------------ forward

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: [B, S, H, D]; rotate the two halves of each head by position."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(cfg: dict, p: dict, x, mm):
    b, s, _ = x.shape
    hd, h, k = head_dim(cfg), cfg["n_heads"], cfg["n_kv_heads"]
    a = rms(x, p["attn_norm"]["scale"], cfg["norm_eps"])
    q = mm(a, p["attn"]["wq"]["w"], "bsd,de->bse").reshape(b, s, h, hd)
    kk = mm(a, p["attn"]["wk"]["w"], "bsd,de->bse").reshape(b, s, k, hd)
    v = mm(a, p["attn"]["wv"]["w"], "bsd,de->bse").reshape(b, s, k, hd)
    q, kk = rope(q, cfg["rope_theta"]), rope(kk, cfg["rope_theta"])
    kk = jnp.repeat(kk, h // k, axis=2)
    v = jnp.repeat(v, h // k, axis=2)
    scores = mm(q, kk, "bqhd,bkhd->bhqk") / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm(probs, v, "bhqk,bkhd->bqhd").reshape(b, s, h * hd)
    x = x + mm(att, p["attn"]["wo"]["w"], "bse,ed->bsd")
    m = rms(x, p["mlp_norm"]["scale"], cfg["norm_eps"])
    gate = mm(m, p["mlp"]["gate"]["w"], "bsd,df->bsf")
    up = mm(m, p["mlp"]["up"]["w"], "bsd,df->bsf")
    return x + mm(jax.nn.silu(gate) * up, p["mlp"]["down"]["w"], "bsf,fd->bsd")


def logits(cfg: dict, params: dict, tokens, first: int, mm=exact):
    """Logits ``[B, S - first, V]`` of positions ``first ..`` of ``tokens``
    ``[B, S]``, layer by layer (each layer recomputed in the backward pass,
    so a gradient holds one layer's activations at a time)."""
    x = params["embed"]["table"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def body(x, p):
        p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
        return block(cfg, p, x, mm), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms(x[:, first:], params["final_norm"]["scale"], cfg["norm_eps"])
    return mm(x, params["lm_head"]["w"].astype(jnp.float32), "bsd,dv->bsv")


# ------------------------------------------------------------- comparisons

def _gap(ref, tokens):
    """How far below the reference's best logit each token's logit lies."""
    picked = jnp.take_along_axis(ref, tokens[..., None], -1)[..., 0]
    return jnp.max(ref, -1) - picked


@functools.partial(jax.jit, static_argnums=(0, 3))
def served_gaps(cfg_items: tuple, params, tokens, first: int, served):
    """Gap of each served token ``served`` ``[B, T]`` at positions
    ``first ..`` of ``tokens`` (the prompt and all but the last served
    token), against the reference."""
    cfg = dict(cfg_items)
    return _gap(logits(cfg, params, tokens, first), served)


@functools.partial(jax.jit, static_argnums=(0, 3))
def control_gaps(cfg_items: tuple, params, tokens, first: int):
    """Gap of the token the fp8 control puts first, at the same positions."""
    cfg = dict(cfg_items)
    ref = logits(cfg, params, tokens, first)
    ctl = logits(cfg, params, tokens, first, mm=fp8)
    return _gap(ref, jnp.argmax(ctl, -1))


def items(cfg: dict) -> tuple:
    """The configuration as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


# ----------------------------------------------------------------- training

def cross_entropy(cfg: dict, params: dict, tokens, labels, mm=exact):
    """Mean next-token loss over every position."""
    lg = logits(cfg, params, tokens, 0, mm)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


@functools.partial(jax.jit, static_argnums=(0, 4), donate_argnums=(5,))
def _block_grad(cfg_items: tuple, params, tokens, labels, control: bool,
                acc):
    """Loss and gradient of one block of rows, added into ``acc``."""
    mm = fp8 if control else exact
    loss, g = jax.value_and_grad(
        lambda p: cross_entropy(dict(cfg_items), p, tokens, labels, mm))(params)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up over ``warmup_steps`` (step ``s`` gets ``(s + 1) /
    warmup`` of the rate), then cosine decay to 0 at ``total_steps``."""
    lr, warm = opt["learning_rate"], max(opt["warmup_steps"], 1)
    if step < opt["warmup_steps"]:
        return lr * (step + 1) / warm
    total = max(opt["total_steps"] - opt["warmup_steps"], 1)
    progress = min(max((step - opt["warmup_steps"]) / total, 0.0), 1.0)
    return 0.5 * lr * (1 + np.cos(np.pi * progress))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, scale, lr, t, b1, b2, eps, wd):
    """One AdamW update (decoupled weight decay, bias-corrected moments)
    of gradients already scaled by ``scale`` (the global-norm clip)."""
    def leaf(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (upd + wd * p), m, v

    flat, treedef = jax.tree_util.tree_flatten(params)
    out = [leaf(*x) for x in zip(flat, *(treedef.flatten_up_to(t)
                                         for t in (grads, m, v)))]
    return tuple(treedef.unflatten([o[i] for o in out]) for i in range(3))


_norms = jax.jit(lambda tree: [jnp.linalg.norm(x)
                               for x in jax.tree_util.tree_leaves(tree)])


def train_readings(cfg: dict, opt: dict, seed: int, data, steps: int,
                   rows_per_block: int, control: bool = False,
                   mesh=None) -> dict:
    """The reference's readings over the first ``steps`` steps from the
    seed's weights on ``data.batch_at(0 ..)``: each step's loss, the
    per-leaf norm of the first clipped gradient, and the per-leaf norm of
    each parameter's change after the last step.  Rows go through in
    blocks; Adam's moments wait on the host while a gradient is computed,
    so the device never holds more than weights, two gradients and one
    block's activations.  With ``mesh`` every parameter, gradient and
    moment is spread over its devices (:func:`spread`), so each holds a
    share of them, and the moments stay there."""
    cfg_items = items(cfg)
    shardings = None if mesh is None else spread(cfg, mesh)
    params = init_params(cfg, seed, shardings=shardings)
    m = v = None
    losses, first_grad = [], None
    for step in range(steps):
        batch = data.batch_at(step)
        tokens, labels = batch["tokens"], batch["labels"]
        n = tokens.shape[0] // rows_per_block
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        total = 0.0
        for i in range(n):
            rows = slice(i * rows_per_block, (i + 1) * rows_per_block)
            loss, acc = _block_grad(cfg_items, params, jnp.asarray(tokens[rows]),
                                    jnp.asarray(labels[rows]), control, acc)
            total += float(loss)
        grads = jax.tree_util.tree_map(lambda g: g / n, acc)
        acc = None
        losses.append(total / n)
        leaf_norms = [float(x) for x in jax.device_get(_norms(grads))]
        gnorm = float(np.sqrt(sum(x * x for x in leaf_norms)))
        scale = min(1.0, opt["max_grad_norm"] / max(gnorm, 1e-9))
        if step == 0:
            first_grad = [x * scale for x in leaf_norms]
            m = jax.tree_util.tree_map(jnp.zeros_like, params)
            v = jax.tree_util.tree_map(jnp.zeros_like, params)
        elif shardings is None:
            m, v = jax.device_put((m, v))
        params, m, v = _adamw(params, grads, m, v, scale, lr_at(opt, step),
                              float(step + 1), opt["beta1"], opt["beta2"],
                              opt["eps"], opt["weight_decay"])
        grads = None
        if shardings is None:
            m, v = jax.device_get((m, v)) if step + 1 < steps else (None, None)
    p0 = init_params(cfg, seed, shardings=shardings)
    change = [float(x) for x in jax.device_get(_norms(
        jax.tree_util.tree_map(jnp.subtract, params, p0)))]
    del p0, params
    return {"losses": losses, "first_grad": first_grad, "change": change}
