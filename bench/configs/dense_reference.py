"""Plain reference of a dense decoder whose attention reads a KQ-SVD cache.

The benchmark's yardstick for every configuration whose ``reference`` is
``dense_reference``: pre-norm blocks of RMSNorm, rotary multi-head (or
grouped-query) attention and a SwiGLU MLP, then a final RMSNorm and an
untied head.  It imports nothing of the program under test.

What lives here:

* ``init_weights``: the benchmark's weights, drawn from a key on the
  device in the type they are served in (bfloat16), in this module's own
  layout (per-layer leaves stacked on a leading layer axis);
* ``hidden``: the forward pass over one sequence in float32 at full
  matmul precision (``mode="f32"``), or with every matmul operand rounded
  to float8 e4m3 under a per-tensor scale (``mode="fp8"``, the control);
  with projections it attends the way KQ-SVD does: keys ``k A_k``,
  queries ``q B_q``, values ``v A_v`` and outputs through ``C_v``;
* ``calibration_grams`` and ``solve_kqsvd``: the projections, from Gram
  matrices of post-rotary queries, keys and values (the paper's Thm 2
  for the key path, App. B for the value path), solved in float64;
* ``gaps_to_tokens`` and ``gaps_to_alt``: how far below the reference's
  best logit a given token (or another precision's first choice) lies.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: largest finite float8 e4m3 magnitude: the per-tensor scale maps each
#: operand's largest entry here
FP8_MAX = 448.0
NEG_INF = -1e30
#: query rows per attention block (bounds the score tile in memory)
Q_BLOCK = 512
#: columns of the head per block when hidden states become logits
V_BLOCK = 16384


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file."""
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    n_layers: int
    rope_theta: float
    rms_eps: float

    @staticmethod
    def of(conf: dict) -> "Dims":
        D, H = conf["hidden_size"], conf["num_attention_heads"]
        return Dims(d_model=D, n_heads=H,
                    n_kv_heads=conf["num_key_value_heads"],
                    d_head=conf.get("head_dim") or D // H,
                    d_ff=conf["intermediate_size"],
                    vocab=conf["vocab_size"],
                    n_layers=conf["num_hidden_layers"],
                    rope_theta=float(conf["rope_theta"]),
                    rms_eps=float(conf["rms_norm_eps"]))

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init_weights(d: Dims, key) -> dict:
    """Gaussian weights scaled by fan-in, unit norm gains, bfloat16.
    Jit it: every leaf is drawn on the device."""
    D, H, Hkv, dh, F, V, L = (d.d_model, d.n_heads, d.n_kv_heads,
                              d.d_head, d.d_ff, d.vocab, d.n_layers)
    ks = jax.random.split(key, 9)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    return {
        "embed": normal(ks[0], (V, D), 0.02),
        "lm_head": normal(ks[1], (D, V), D ** -0.5),
        "final_norm": ones((D,)),
        "ln1": ones((L, D)),
        "ln2": ones((L, D)),
        "wq": normal(ks[2], (L, D, H, dh), D ** -0.5),
        "wk": normal(ks[3], (L, D, Hkv, dh), D ** -0.5),
        "wv": normal(ks[4], (L, D, Hkv, dh), D ** -0.5),
        "wo": normal(ks[5], (L, H, dh, D), (H * dh) ** -0.5),
        "wi": normal(ks[6], (L, D, F), D ** -0.5),
        "wg": normal(ks[7], (L, D, F), D ** -0.5),
        "wdown": normal(ks[8], (L, F, D), F ** -0.5),
    }


LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi", "wg", "wdown")
PROJ_KEYS = ("a_k", "b_q", "a_v", "c_v")


# ---------------------------------------------------------------------------
# arithmetic in the chosen precision
# ---------------------------------------------------------------------------


def _fp8(x, scale=None):
    """Round to float8 e4m3 under a per-tensor scale (the largest entry
    maps to ``FP8_MAX`` unless ``scale`` is given); back in float32."""
    x = x.astype(jnp.float32)
    if scale is None:
        scale = jnp.max(jnp.abs(x)) / FP8_MAX
    scale = jnp.maximum(scale, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ein(spec: str, a, b, mode: str, b_scale=None):
    """einsum in float32 at full precision; with ``mode="fp8"`` both
    operands are first rounded to float8 (``b`` under ``b_scale`` when
    it is a block of a larger tensor)."""
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b, b_scale)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * gain.astype(jnp.float32)


def _rope(x, theta: float):
    """Rotate-half rotary embedding; x: (S, heads, d) at positions 0..S-1."""
    S, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale: float, mode: str):
    """Causal attention; q: (S, H, r), k: (S, H, r), v: (S, H, rv)."""
    S = q.shape[0]
    blk = min(Q_BLOCK, S)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        s = _ein("qhr,khr->hqk", qi, k, mode) * scale
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(S)[None, None, :] <= qpos[None, :, None],
                      s, NEG_INF)
        return _ein("hqk,khr->qhr", jax.nn.softmax(s, axis=-1), v, mode)

    out = jax.lax.map(one, jnp.arange(S // blk))
    return out.reshape(S, q.shape[1], v.shape[-1])


def _layer(d: Dims, x, lw, lp, mode: str):
    """One block; ``lp`` holds the layer's projections or is None (then
    full attention, and the Gram statistics come back too)."""
    S = x.shape[0]
    Hkv, m, dh = d.n_kv_heads, d.group, d.d_head
    h = _rms(x, lw["ln1"], d.rms_eps)
    q = _rope(_ein("sd,dhe->she", h, lw["wq"], mode), d.rope_theta)
    k = _rope(_ein("sd,dhe->she", h, lw["wk"], mode), d.rope_theta)
    v = _ein("sd,dhe->she", h, lw["wv"], mode)
    scale = dh ** -0.5
    grams = None
    if lp is None:
        out = _attend(q, jnp.repeat(k, m, 1), jnp.repeat(v, m, 1), scale,
                      mode)
        y = _ein("she,hed->sd", out, lw["wo"], mode)
        hp = jax.lax.Precision.HIGHEST
        qg = q.reshape(S, Hkv, m, dh)
        grams = (jnp.einsum("sgd,sge->gde", k, k, precision=hp),
                 jnp.einsum("sgmd,sgme->gde", qg, qg, precision=hp),
                 jnp.einsum("sgd,sge->gde", v, v, precision=hp))
    else:
        kc = _ein("sgd,gdr->sgr", k, lp["a_k"], mode)
        vc = _ein("sgd,gdr->sgr", v, lp["a_v"], mode)
        qc = _ein("sgmd,gdr->sgmr", q.reshape(S, Hkv, m, dh), lp["b_q"],
                  mode).reshape(S, d.n_heads, -1)
        agg = _attend(qc, jnp.repeat(kc, m, 1), jnp.repeat(vc, m, 1),
                      scale, mode)
        c_v = lp["c_v"].reshape(Hkv, -1, m, d.d_model)
        y = _ein("sgmr,grmd->sd", agg.reshape(S, Hkv, m, -1), c_v, mode)
    x = x + y
    h = _rms(x, lw["ln2"], d.rms_eps)
    a = _ein("sd,df->sf", h, lw["wi"], mode)
    g = _ein("sd,df->sf", h, lw["wg"], mode)
    x = x + _ein("sf,fd->sd", jax.nn.silu(g) * a, lw["wdown"], mode)
    return x, grams


def _stack(d: Dims, w, proj, tokens, mode: str):
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    xs = {"w": {k: w[k] for k in LAYER_KEYS}}
    if proj is not None:
        xs["p"] = {k: proj[k] for k in PROJ_KEYS}

    def body(x, lx):
        x, grams = _layer(d, x, lx["w"], lx.get("p"), mode)
        return x, grams

    x, grams = jax.lax.scan(body, x, xs)
    return _rms(x, w["final_norm"], d.rms_eps), grams


@functools.partial(jax.jit, static_argnums=(0, 4))
def hidden(d: Dims, w, proj, tokens, mode: str = "f32"):
    """Final hidden states (S, D) of one sequence; S a multiple of
    ``Q_BLOCK`` (pad at the end: attention is causal, so padding changes
    no earlier row)."""
    with jax.default_matmul_precision("highest"):
        return _stack(d, w, proj, tokens, mode)[0]


@functools.partial(jax.jit, static_argnums=(0,))
def calibration_grams(d: Dims, w, tokens):
    """Per-layer Gram matrices (L, Hkv, dh, dh) of keys, group-stacked
    queries and values over one calibration sequence, full attention."""
    with jax.default_matmul_precision("highest"):
        return _stack(d, w, None, tokens, "f32")[1]


def _vocab_block(V: int) -> int:
    """A divisor of the vocabulary near ``V_BLOCK``: the head's columns
    are taken a block at a time, so no float32 copy of it is ever whole."""
    nb = -(-V // V_BLOCK)
    while V % nb:
        nb += 1
    return V // nb


def _head_blocks(w, body, init):
    """Scan ``body(carry, logits_of(hidden, alt, mode), offset)`` over the
    head's column blocks."""
    head = w["lm_head"]
    blk = _vocab_block(head.shape[1])

    def step(carry, i):
        wb = jax.lax.dynamic_slice_in_dim(head, i * blk, blk, 1)
        return body(carry, wb, i * blk), None

    return jax.lax.scan(step, init, jnp.arange(head.shape[1] // blk))[0]


@jax.jit
def gaps_to_tokens(w, hid, next_tokens):
    """Per row: the reference's best logit minus its logit of
    ``next_tokens`` (0 where that token is the reference's first)."""
    def body(carry, wb, off):
        best, at = carry
        lg = _ein("sd,dv->sv", hid, wb, "f32")
        local = next_tokens - off
        hit = (local >= 0) & (local < wb.shape[1])
        got = jnp.take_along_axis(
            lg, jnp.clip(local, 0, wb.shape[1] - 1)[:, None], 1)[:, 0]
        return jnp.maximum(best, lg.max(-1)), jnp.where(hit, got, at)

    n = hid.shape[0]
    best, at = _head_blocks(w, body, (jnp.full((n,), -jnp.inf),
                                      jnp.zeros((n,))))
    return best - at


@functools.partial(jax.jit, static_argnums=(3,))
def gaps_to_alt(w, hid, hid_alt, mode: str):
    """Per row: how far below the reference's best logit lies the token
    that the ``mode`` computation (hidden states ``hid_alt``) puts first."""
    wscale = jnp.max(jnp.abs(w["lm_head"])).astype(jnp.float32) / FP8_MAX

    def body(carry, wb, off):
        best, alt_best, at_alt = carry
        lg = _ein("sd,dv->sv", hid, wb, "f32")
        la = _ein("sd,dv->sv", hid_alt, wb, mode, b_scale=wscale)
        arg = la.argmax(-1)
        top = jnp.take_along_axis(la, arg[:, None], 1)[:, 0]
        ref = jnp.take_along_axis(lg, arg[:, None], 1)[:, 0]
        better = top > alt_best
        return (jnp.maximum(best, lg.max(-1)),
                jnp.where(better, top, alt_best),
                jnp.where(better, ref, at_alt))

    n = hid.shape[0]
    best, _, at_alt = _head_blocks(w, body, (jnp.full((n,), -jnp.inf),
                                             jnp.full((n,), -jnp.inf),
                                             jnp.zeros((n,))))
    return best - at_alt


# ---------------------------------------------------------------------------
# KQ-SVD projections (float64, on the host)
# ---------------------------------------------------------------------------


def _factors(G):
    """Right singular vectors and values of a matrix from its Gram."""
    w, V = np.linalg.eigh(0.5 * (G + G.T))
    order = np.argsort(w)[::-1]
    return V[:, order], np.sqrt(np.clip(w[order], 0.0, None))


def _pinv(s):
    return np.where(s > 1e-12 * s.max(), 1.0 / np.maximum(s, 1e-300), 0.0)


def solve_kqsvd(d: Dims, grams, wo, rank_k: int, rank_v: int) -> dict:
    """Fixed-rank KQ-SVD factors for every layer and kv head.

    grams: (g_k, g_q, g_v), each (L, Hkv, dh, dh); wo: (L, H, dh, D).
    Keys: with K = U_K S_K V_K^T and Q likewise, the top ``rank_k`` left
    singular vectors U' of M = S_K V_K^T V_Q S_Q give A = V_K S_K^-1 U',
    B = V_K S_K U', so (q B)(k A)^T is the best rank-``rank_k`` fit of
    q k^T.  Values: with N = S_V V_V^T W (W the group's output weights),
    N = U S V^T gives A_v = V_V S_V^-1 U_r and C = U_r^T N.
    Returns float32 arrays a_k, b_q (L, Hkv, dh, rank_k), a_v
    (L, Hkv, dh, rank_v), c_v (L, Hkv, rank_v, m * D)."""
    g_k, g_q, g_v = (np.asarray(g, np.float64) for g in grams)
    L, Hkv, m, dh, D = d.n_layers, d.n_kv_heads, d.group, d.d_head, \
        d.d_model
    out = {"a_k": np.zeros((L, Hkv, dh, rank_k)),
           "b_q": np.zeros((L, Hkv, dh, rank_k)),
           "a_v": np.zeros((L, Hkv, dh, rank_v)),
           "c_v": np.zeros((L, Hkv, rank_v, m * D))}
    for l in range(L):
        wl = np.asarray(wo[l], np.float64).reshape(Hkv, m, dh, D)
        for g in range(Hkv):
            Vk, sk = _factors(g_k[l, g])
            Vq, sq = _factors(g_q[l, g])
            U, _, _ = np.linalg.svd((sk[:, None] * (Vk.T @ Vq))
                                    * sq[None, :])
            Ur = U[:, :rank_k]
            out["a_k"][l, g] = Vk @ (_pinv(sk)[:, None] * Ur)
            out["b_q"][l, g] = Vk @ (sk[:, None] * Ur)
            Vv, sv = _factors(g_v[l, g])
            W = wl[g].transpose(1, 0, 2).reshape(dh, m * D)
            N = sv[:, None] * (Vv.T @ W)
            e, Un = np.linalg.eigh(N @ N.T)
            Ur = Un[:, np.argsort(e)[::-1][:rank_v]]
            out["a_v"][l, g] = Vv @ (_pinv(sv)[:, None] * Ur)
            out["c_v"][l, g] = Ur.T @ N
    return {k: v.astype(np.float32) for k, v in out.items()}
