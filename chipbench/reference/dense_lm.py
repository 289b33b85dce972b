"""Plain float32 reference of the dense pre-norm decoder LM.

It covers the configurations whose blocks are all full causal attention
followed by a dense MLP (``block_pattern`` ``["attn"]``): the paper's own
LMs and stablelm-1.6b.  Written from the architecture's equations in
straightforward ``jax.numpy``, with every matrix product at
``Precision.HIGHEST``; it imports nothing of the program under test.

The parameter tree uses the program's key names, so that weights made here
from the seed can be handed to the program and to this reference alike:

    embed/table (V, d), embed/norm/{scale,bias}      (stable embedding)
    blocks/b0_attn/norm1/{scale,bias}                (leading layer dim L)
    blocks/b0_attn/attn/{wq,wk,wv,wo}
    blocks/b0_attn/mlp/{w_gate,w_in,w_out}           (w_gate iff gated)
    blocks/b0_attn/norm2/{scale,bias}
    final_norm/{scale,bias}, head/w (d, V)           (head iff untied)

``bias`` exists for ``layernorm`` only.  Departures from the published
models are those of the configuration as the program runs it: GELU is the
tanh approximation (``jax.nn.gelu``'s default), rotary embeddings rotate
the whole head in two halves, and every norm uses eps 1e-6.

``precision="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 e4m3 under a per-tensor absmax scale (f32 accumulation),
and its incoming gradient rounded to float8 e5m2 the same way: the next
precision below the configuration's bf16 compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
GRAD8 = jnp.float8_e5m2
GRAD8_MAX = 57344.0


def check_supported(cfg: dict) -> None:
    """Refuse configurations whose blocks this reference does not model."""
    unsupported = {
        "block_pattern": (list(cfg.get("block_pattern", ["attn"])), ["attn"]),
        "n_experts": (cfg.get("n_experts", 0), 0),
        "parallel_block": (cfg.get("parallel_block", False), False),
        "attn_type": (cfg.get("attn_type", "full"), "full"),
        "qkv_bias": (cfg.get("qkv_bias", False), False),
        "use_bias": (cfg.get("use_bias", False), False),
        "frontend": (cfg.get("frontend", "none"), "none"),
    }
    for key, (have, want) in unsupported.items():
        if have != want:
            raise NotImplementedError(
                f"dense_lm reference: {key}={have!r} is not modelled "
                f"(needs {want!r})")


# ------------------------------------------------------------- weights

def param_shapes(cfg: dict) -> dict:
    """The parameter tree as shapes (tuples), in the program's key names."""
    check_supported(cfg)
    d, V, L = cfg["d_model"], cfg["vocab_size"], cfg["n_layers"]
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    Dh = cfg.get("head_dim") or d // H
    f = cfg["d_ff"]
    layer = cfg.get("norm_type", "rmsnorm") == "layernorm"

    def norm(lead=()):
        out = {"scale": lead + (d,)}
        if layer:
            out["bias"] = lead + (d,)
        return out

    mlp = {"w_in": (L, d, f), "w_out": (L, f, d)}
    if cfg.get("gated_mlp", True):
        mlp["w_gate"] = (L, d, f)
    block = {"norm1": norm((L,)), "norm2": norm((L,)),
             "attn": {"wq": (L, d, H * Dh), "wk": (L, d, KV * Dh),
                      "wv": (L, d, KV * Dh), "wo": (L, H * Dh, d)},
             "mlp": mlp}
    embed = {"table": (V, d)}
    if cfg.get("stable_embedding", True):
        embed["norm"] = {"scale": (d,), "bias": (d,)}
    tree = {"embed": embed, "blocks": {"b0_attn": block},
            "final_norm": norm()}
    if not cfg.get("tie_embeddings", False):
        tree["head"] = {"w": (d, V)}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, int) for e in x)


def leaf_paths(tree) -> list:
    """'a/b/c' path strings of a parameter tree, in flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)
    return ["/".join(str(k.key) for k in path) for path, _ in flat]


def _init_leaf(key, path: str, shape: tuple, cfg: dict) -> jax.Array:
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    if path == "embed/table":
        if cfg.get("stable_embedding", True):        # Xavier uniform
            lim = np.sqrt(6.0 / (shape[0] + shape[1]))
            return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[1])
    # matrices: N(0, 1/fan_in), fan_in the second-to-last dim
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Seeded weights (f32), one ``fold_in`` of ``key`` per leaf.  Call it
    under ``jax.jit`` so the whole tree is made on the device at once."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    paths = leaf_paths(shapes)
    leaves = [_init_leaf(jax.random.fold_in(key, i), p, s, cfg)
              for i, (p, s) in enumerate(zip(paths, flat))]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_key(seed: int) -> jax.Array:
    """PRNG key for any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


# ------------------------------------------------------------- forward

def _round(x, dtype, top):
    """x rounded to ``dtype`` under a per-tensor absmax scale."""
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _round(a, FP8, FP8_MAX), _round(b, FP8, FP8_MAX),
                      precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    qa, qb = _round(a, FP8, FP8_MAX), _round(b, FP8, FP8_MAX)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(spec, res, ct):
    """Gradients through the rounded inputs, the incoming gradient itself
    rounded to float8 e5m2 (the usual fp8 training recipe)."""
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_round(ct, GRAD8, GRAD8_MAX))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(precision):
    def mm(spec, a, b):
        if precision == "fp8":
            return _fp8_einsum(spec, a, b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _norm(p, x, norm_type):
    if norm_type == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + NORM_EPS) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + NORM_EPS) * p["scale"]


def _rope(x, positions, theta):
    """Rotate the two halves of each head: x (B, S, H, D), positions (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg, precision, x, p, positions):
    mm = _mm(precision)
    norm_type = cfg.get("norm_type", "rmsnorm")
    B, S, d = x.shape
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    Dh = cfg.get("head_dim") or d // H
    G = H // KV
    h = _norm(p["norm1"], x, norm_type)
    a = p["attn"]
    q = mm("bsd,de->bse", h, a["wq"]).reshape(B, S, H, Dh)
    k = mm("bsd,de->bse", h, a["wk"]).reshape(B, S, KV, Dh)
    v = mm("bsd,de->bse", h, a["wv"]).reshape(B, S, KV, Dh)
    theta = float(cfg.get("rope_theta", 10000.0))
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q = q.reshape(B, S, KV, G, Dh) / np.sqrt(Dh)
    scores = mm("bskgd,btkd->bkgst", q, k)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mm("bkgst,btkd->bskgd", probs, v).reshape(B, S, H * Dh)
    x = x + mm("bse,ed->bsd", att, a["wo"])
    h2 = _norm(p["norm2"], x, norm_type)
    m = p["mlp"]
    if "w_gate" in m:
        hid = jax.nn.silu(mm("bsd,df->bsf", h2, m["w_gate"])) * \
            mm("bsd,df->bsf", h2, m["w_in"])
    else:
        hid = jax.nn.gelu(mm("bsd,df->bsf", h2, m["w_in"]), approximate=True)
    return x + mm("bsf,fd->bsd", hid, m["w_out"])


def logits(cfg: dict, params: dict, tokens: jax.Array,
           precision: str = "f32") -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, V) f32."""
    mm = _mm(precision)
    emb = params["embed"]
    x = emb["table"][tokens]
    if "norm" in emb:
        x = _norm(emb["norm"], x, "layernorm")
    else:
        x = x * np.sqrt(cfg["d_model"])
    positions = jnp.arange(tokens.shape[1])

    def body(h, p):
        return _block(cfg, precision, h, p, positions), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["b0_attn"])
    x = _norm(params["final_norm"], x, cfg.get("norm_type", "rmsnorm"))
    w = params["head"]["w"] if "head" in params else emb["table"].T
    return mm("bsd,dv->bsv", x, w)


def loss(cfg: dict, params: dict, tokens: jax.Array,
         precision: str = "f32") -> jax.Array:
    """Mean next-token NLL of tokens (B, S+1)."""
    lg = logits(cfg, params, tokens[:, :-1], precision)
    labels = tokens[:, 1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _loss_and_grads_jit(cfg_items, params, tokens, precision):
    cfg = dict(cfg_items)
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens, precision))(params)


def freeze(cfg: dict) -> tuple:
    """Hashable form of a configuration dict (static jit argument)."""
    def fz(v):
        if isinstance(v, list):
            return tuple(fz(e) for e in v)
        if isinstance(v, dict):
            return tuple(sorted((k, fz(e)) for k, e in v.items()))
        return v
    return tuple(sorted((k, fz(v)) for k, v in cfg.items()))


def loss_and_grads(cfg: dict, params: dict, tokens: jax.Array, *,
                   rows_per_block: int, precision: str = "f32"):
    """Loss and gradients of the batch mean, taken in blocks of rows so
    that the float32 activations fit beside the weights."""
    n = tokens.shape[0]
    if n % rows_per_block:
        raise ValueError(f"batch {n} is not a multiple of {rows_per_block}")
    total, grads = 0.0, None
    for i in range(0, n, rows_per_block):
        l, g = _loss_and_grads_jit(freeze(cfg), params,
                                   tokens[i:i + rows_per_block], precision)
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add,
                                                               grads, g)
    k = n // rows_per_block
    return total / k, jax.tree_util.tree_map(lambda g: g / k, grads)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _seq_logits_jit(cfg_items, params, tokens, precision):
    return logits(dict(cfg_items), params, tokens, precision)[0]


def sequence_logits(cfg: dict, params: dict, tokens: np.ndarray,
                    pad_to: int, precision: str = "f32") -> jax.Array:
    """Logits (len, V) of one token sequence, padded on the right to
    ``pad_to`` so that every length shares one compiled program (causal
    attention keeps the padding out of the real positions)."""
    n = len(tokens)
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :n] = tokens
    return _seq_logits_jit(freeze(cfg), params, jnp.asarray(buf),
                           precision)[:n]
