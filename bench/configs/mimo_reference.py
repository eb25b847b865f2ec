"""Plain reference of MiMo-V2-Flash's language model, cut as its
configuration file says, with attention reading a KQ-SVD cache.

The benchmark's yardstick for configurations whose ``reference`` is
``mimo_reference``; it follows the published description
(https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash) and imports nothing of
the program under test.  Per layer: RMSNorm, attention of the layer's
kind, RMSNorm, then layer 0's dense SwiGLU MLP or the expert layer.

* Attention kinds (``hybrid_layer_pattern``, 0 full, 1 window): full
  layers have ``num_key_value_heads`` KV heads and RoPE theta
  ``rope_theta``; window layers ``swa_num_key_value_heads`` KV heads,
  RoPE theta ``swa_rope_theta``, attend the last ``sliding_window``
  positions and have a per-head sink logit (GPT-oss style: softmax
  weights ``exp(s_j) / (sum_j exp(s_j) + exp(sink_h))``, no value).
  q/k heads are ``head_dim`` wide, v heads ``v_head_dim``; RoPE (rotate
  half) turns the first ``round(partial_rotary_factor * head_dim)``
  q/k dims; scores are scaled by ``head_dim ** -0.5``; the values are
  multiplied by ``attention_value_scale``.
* The expert layer: sigmoid scores over all ``published`` routed
  experts, the top ``num_experts_per_tok`` chosen by score plus a
  selection bias, their scores normalised over the chosen; this chip
  holds experts ``[0, n_routed_experts)`` and adds only their weighted
  SwiGLU outputs (the deployment's other chips add the rest).
* Near-ties of the router: a held expert whose score plus bias lies
  within ``ROUTE_MARGIN`` of the boundary between the chosen and the
  rest may be chosen the other way by any computation less precise than
  this one, and that adds or drops a whole expert's output.  ``hidden``
  returns, beside the hidden states, each position's smallest such
  distance over the expert layers, and the gaps are not scored where it
  lies under ``ROUTE_MARGIN`` (``gaps_to_tokens``).
* With projections, attention runs as KQ-SVD does: post-RoPE keys
  ``k A_k``, queries ``q B_q``, values ``v A_v`` and outputs through
  ``C_v``, per kind.

Departures from the published model: the 3 MTP draft modules are not
run (they are not part of the forward pass); ``attention_chunk_size`` is
taken as an implementation detail of the published code and not
modelled.  The weights are random from the seed (``init_weights``).

Interface as ``dense_reference``: ``Dims.of``, ``init_weights``,
``calibration_grams``, ``solve_kqsvd``, ``hidden`` (``"f32"`` or the
float8 control ``"fp8"``; it returns the hidden states and the router
margins), ``gaps_to_tokens``, ``gaps_to_alt``.  The
projections are per kind: keys ``"a_k.full"``, ``"a_k.window"``, and so
on, each stacked over that kind's layers; weights and projections also
carry ``layer_kind`` (the pattern).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs import dense_reference
from bench.configs.dense_reference import _ein, _factors, _pinv, _rms

__all__ = ["Dims", "init_weights", "calibration_grams", "solve_kqsvd",
           "hidden", "gaps_to_tokens", "gaps_to_alt"]

NEG_INF = -1e30
#: query rows per attention block (bounds the score tile in memory)
Q_BLOCK = 128
KINDS = ("full", "window")
PROJ_KEYS = ("a_k", "b_q", "a_v", "c_v")
#: positions whose router margin (``_experts``) is under this are not
#: scored: on the chip every served gap above 0.1 sat at a margin under
#: 4.3e-4 (PERF.md §2)
ROUTE_MARGIN = 1e-3


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file."""
    d_model: int
    n_heads: int
    kv_full: int
    kv_window: int
    d_qk: int
    d_v: int
    rotary: int
    theta_full: float
    theta_window: float
    window: int
    sink: bool
    value_scale: float
    d_ff: int
    d_expert: int
    n_experts: int           # the router's outputs (published count)
    n_held: int              # experts held here: [0, n_held)
    top_k: int
    vocab: int
    n_layers: int
    pattern: tuple           # 0 full, 1 window, per layer
    dense: tuple             # True where the layer has the dense MLP
    rms_eps: float

    @staticmethod
    def of(conf: dict) -> "Dims":
        L = conf["num_hidden_layers"]
        dqk = conf["head_dim"]
        return Dims(
            d_model=conf["hidden_size"],
            n_heads=conf["num_attention_heads"],
            kv_full=conf["num_key_value_heads"],
            kv_window=conf["swa_num_key_value_heads"],
            d_qk=dqk, d_v=conf["v_head_dim"],
            rotary=int(round(conf["partial_rotary_factor"] * dqk)),
            theta_full=float(conf["rope_theta"]),
            theta_window=float(conf["swa_rope_theta"]),
            window=conf["sliding_window"],
            sink=bool(conf["add_swa_attention_sink_bias"]),
            value_scale=float(conf["attention_value_scale"]),
            d_ff=conf["intermediate_size"],
            d_expert=conf["moe_intermediate_size"],
            n_experts=conf["published"]["n_routed_experts"],
            n_held=conf["n_routed_experts"],
            top_k=conf["num_experts_per_tok"],
            vocab=conf["vocab_size"], n_layers=L,
            pattern=tuple(conf["hybrid_layer_pattern"][:L]),
            dense=tuple(not f for f in conf["moe_layer_freq"][:L]),
            rms_eps=float(conf["layernorm_epsilon"]))

    def kv(self, kind: str) -> int:
        return self.kv_full if kind == "full" else self.kv_window

    def layers(self, kind: str) -> list:
        """Layer ids of a kind, in order."""
        want = 0 if kind == "full" else 1
        return [i for i, k in enumerate(self.pattern) if k == want]

    @property
    def moe_layers(self) -> list:
        return [i for i in range(self.n_layers) if not self.dense[i]]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init_weights(d: Dims, key) -> dict:
    """Gaussian weights scaled by fan-in, unit norm gains, bfloat16; the
    sink logits standard normal, the router scaled so that its logits
    spread over a few units, the selection bias zero (``noaux_tc``
    trains it to balance the experts' load, and a random router is
    balanced without it).
    Leaves are per layer, ``<name>.<layer>`` (the kinds differ in
    shape), except ``wo`` (the same shape in both kinds), stacked over
    all layers; ``layer_kind`` holds the pattern.  Jit it."""
    D, H, dqk, dv, V, L = (d.d_model, d.n_heads, d.d_qk, d.d_v, d.vocab,
                           d.n_layers)
    E, Eh, F, Fe = d.n_experts, d.n_held, d.d_ff, d.d_expert
    keys = iter(jax.random.split(key, 4 + 10 * L))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    w = {"embed": normal((V, D), 0.02), "lm_head": normal((D, V), D ** -0.5),
         "final_norm": ones((D,)),
         "wo": normal((L, H, dv, D), (H * dv) ** -0.5),
         "layer_kind": jnp.asarray(d.pattern, jnp.int32)}
    for i in range(L):
        hkv = d.kv(KINDS[d.pattern[i]])
        w.update({f"ln1.{i}": ones((D,)), f"ln2.{i}": ones((D,)),
                  f"wq.{i}": normal((D, H, dqk), D ** -0.5),
                  f"wk.{i}": normal((D, hkv, dqk), D ** -0.5),
                  f"wv.{i}": normal((D, hkv, dv), D ** -0.5)})
        if d.pattern[i] and d.sink:
            w[f"sink.{i}"] = normal((H,), 1.0)
        if d.dense[i]:
            w.update({f"wi.{i}": normal((D, F), D ** -0.5),
                      f"wg.{i}": normal((D, F), D ** -0.5),
                      f"wdown.{i}": normal((F, D), F ** -0.5)})
        else:
            w.update({f"router.{i}": normal((D, E), 2 * D ** -0.5),
                      f"bias.{i}": jnp.zeros((E,), jnp.bfloat16),
                      f"wi_e.{i}": normal((Eh, D, Fe), D ** -0.5),
                      f"wg_e.{i}": normal((Eh, D, Fe), D ** -0.5),
                      f"wo_e.{i}": normal((Eh, Fe, D), Fe ** -0.5)})
    return w


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _rope(x, theta: float, rotary: int):
    """Rotate-half RoPE on the first ``rotary`` dims of x: (S, heads, d)
    at positions 0..S-1."""
    S = x.shape[0]
    inv = 1.0 / (theta ** (np.arange(0, rotary, 2, dtype=np.float64)
                           / rotary))
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = rotary // 2
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attend(q, k, v, scale: float, mode: str, window: int = 0, sink=None):
    """Causal attention by query blocks, grouped: q (S, G, m, r),
    k (S, G, r), v (S, G, rv); with ``window`` a query at p sees keys in
    (p - window, p]; ``sink`` (G, m) sink logits.  (S, G, m, rv)."""
    S, G, m, _ = q.shape
    blk = min(Q_BLOCK, S)
    # a window block reads only its own keys and the window before them
    span = S if not window else min(S, blk + window)
    pad = span - blk if window else 0
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        first = i * blk if window else 0          # in padded coordinates
        ki = jax.lax.dynamic_slice_in_dim(kp, first, span, 0)
        vi = jax.lax.dynamic_slice_in_dim(vp, first, span, 0)
        kpos = first - pad + jnp.arange(span)
        qpos = i * blk + jnp.arange(blk)
        s = _ein("qgmr,kgr->gmqk", qi, ki, mode) * scale
        d = qpos[:, None] - kpos[None, :]
        ok = (d >= 0) & (kpos[None, :] >= 0)
        if window:
            ok = ok & (d < window)
        s = jnp.where(ok[None, None], s, NEG_INF)
        top = s.max(-1)
        if sink is not None:
            top = jnp.maximum(top, sink[:, :, None])
        e = jnp.exp(s - top[..., None])
        den = e.sum(-1)
        if sink is not None:
            den = den + jnp.exp(sink[:, :, None] - top)
        return _ein("gmqk,kgr->qgmr", e / den[..., None], vi, mode)

    out = jax.lax.map(one, jnp.arange(S // blk))
    return out.reshape(S, G, m, v.shape[-1])


def _attention(d: Dims, kind: str, h, lw, lp, mode: str):
    """One attention sublayer of ``kind``; ``lp`` its projections or
    None (then uncompressed, and the Gram statistics come back too)."""
    S = h.shape[0]
    G = d.kv(kind)
    m = d.n_heads // G
    theta = d.theta_full if kind == "full" else d.theta_window
    window = 0 if kind == "full" else d.window
    sink = lw.get("sink")
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(G, m)
    q = _rope(_ein("sd,dhe->she", h, lw["wq"], mode), theta, d.rotary)
    k = _rope(_ein("sd,dhe->she", h, lw["wk"], mode), theta, d.rotary)
    v = _ein("sd,dhe->she", h, lw["wv"], mode) * d.value_scale
    qg = q.reshape(S, G, m, d.d_qk)
    scale = d.d_qk ** -0.5
    if lp is None:
        out = _attend(qg, k, v, scale, mode, window, sink)
        y = _ein("she,hed->sd", out.reshape(S, d.n_heads, d.d_v),
                 lw["wo"], mode)
        hp = jax.lax.Precision.HIGHEST
        grams = (jnp.einsum("sgd,sge->gde", k, k, precision=hp),
                 jnp.einsum("sgmd,sgme->gde", qg, qg, precision=hp),
                 jnp.einsum("sgd,sge->gde", v, v, precision=hp))
        return y, grams
    kc = _ein("sgd,gdr->sgr", k, lp["a_k"], mode)
    vc = _ein("sgd,gdr->sgr", v, lp["a_v"], mode)
    qc = _ein("sgmd,gdr->sgmr", qg, lp["b_q"], mode)
    agg = _attend(qc, kc, vc, scale, mode, window, sink)
    c_v = lp["c_v"].reshape(G, -1, m, d.d_model)
    return _ein("sgmr,grmd->sd", agg, c_v, mode), None


def _experts(d: Dims, h, lw, mode: str):
    """The held experts' share of the expert layer for every token, and
    each token's router margin: how far the score plus bias of the held
    expert nearest the boundary lies from it (from the last chosen
    expert's if it was not chosen, from the first unchosen one's if it
    was)."""
    logits = _ein("sd,de->se", h, lw["router"], mode)
    scores = jax.nn.sigmoid(logits)
    keys = scores + lw["bias"].astype(jnp.float32)
    top, idx = jax.lax.top_k(keys, d.top_k + 1)
    idx = idx[:, :d.top_k]
    wts = jnp.take_along_axis(scores, idx, 1)
    wts = wts / wts.sum(-1, keepdims=True)
    # (S, n_held): the weight of each held expert, 0 where not chosen
    gate = jnp.zeros((h.shape[0], d.n_experts)).at[
        jnp.arange(h.shape[0])[:, None], idx].set(wts)[:, :d.n_held]
    held = keys[:, :d.n_held]
    chosen = (idx[:, :, None] == jnp.arange(d.n_held)).any(1)
    margin = jnp.where(chosen, held - top[:, -1:],
                       top[:, -2:-1] - held).min(-1)

    def one(y, ew):
        wi, wg, wo, ge = ew
        a = _ein("sd,df->sf", h, wi, mode)
        g = _ein("sd,df->sf", h, wg, mode)
        o = _ein("sf,fd->sd", jax.nn.silu(g) * a, wo, mode)
        return y + ge[:, None] * o, None

    y = jax.lax.scan(one, jnp.zeros_like(h),
                     (lw["wi_e"], lw["wg_e"], lw["wo_e"], gate.T))[0]
    return y, margin


def _dense_mlp(h, lw, mode: str):
    a = _ein("sd,df->sf", h, lw["wi"], mode)
    g = _ein("sd,df->sf", h, lw["wg"], mode)
    return _ein("sf,fd->sd", jax.nn.silu(g) * a, lw["wdown"], mode)


def _layer_weights(d: Dims, w, proj, i: int):
    """(kind, attention weights, projections, FFN weights) of layer i."""
    kind = KINDS[d.pattern[i]]
    aw = {k: w[f"{k}.{i}"] for k in ("wq", "wk", "wv", "sink")
          if f"{k}.{i}" in w}
    aw["wo"] = w["wo"][i]
    j = d.layers(kind).index(i)
    lp = (None if proj is None
          else {k: proj[f"{k}.{kind}"][j] for k in PROJ_KEYS})
    names = (("wi", "wg", "wdown") if d.dense[i]
             else ("router", "bias", "wi_e", "wg_e", "wo_e"))
    return kind, aw, lp, {k: w[f"{k}.{i}"] for k in names}


def _stack(d: Dims, w, proj, tokens, mode: str):
    """Final hidden states, the router margins (the smallest over the
    expert layers, per position) and per kind the layers' Gram
    statistics (uncompressed only).  Layers are unrolled: they differ
    in kind."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    grams = {kind: [] for kind in KINDS}
    margin = jnp.full(tokens.shape, jnp.inf)
    for i in range(d.n_layers):
        kind, aw, lp, fw = _layer_weights(d, w, proj, i)
        h = _rms(x, w[f"ln1.{i}"], d.rms_eps)
        y, g = _attention(d, kind, h, aw, lp, mode)
        if g is not None:
            grams[kind].append(g)
        x = x + y
        h = _rms(x, w[f"ln2.{i}"], d.rms_eps)
        if d.dense[i]:
            x = x + _dense_mlp(h, fw, mode)
        else:
            y, m = _experts(d, h, fw, mode)
            x, margin = x + y, jnp.minimum(margin, m)
    return _rms(x, w["final_norm"], d.rms_eps), margin, grams


@functools.partial(jax.jit, static_argnums=(0, 4))
def _hidden(d: Dims, w, proj, tokens, mode: str):
    with jax.default_matmul_precision("highest"):
        return _stack(d, w, proj, tokens, mode)[:2]


def hidden(d: Dims, w, proj, tokens, mode: str = "f32"):
    """Final hidden states (S, D) of one sequence and its router margins
    (S,); S a multiple of ``Q_BLOCK``.  Computed padded at the end to a
    power of two of at least 1024 tokens, so that a cell's checks
    compile a few shapes: attention is causal and the expert layer works
    per token, so padding changes no earlier row."""
    S = tokens.shape[0]
    n = max(1024, 1 << (S - 1).bit_length())
    h, margin = _hidden(d, w, proj, jnp.pad(tokens, (0, n - S)), mode)
    return h[:S], margin[:S]


def _scored(margin, gaps, route_margin: float):
    return jnp.where(margin >= route_margin, gaps, 0.0)


def gaps_to_tokens(w, hid, next_tokens, route_margin: float = ROUTE_MARGIN):
    """``dense_reference.gaps_to_tokens`` on the hidden states of
    ``hid``, 0 where the router margin is under ``route_margin`` (0
    scores every position)."""
    h, margin = hid
    return _scored(margin, dense_reference.gaps_to_tokens(w, h, next_tokens),
                   route_margin)


def gaps_to_alt(w, hid, hid_alt, mode: str,
                route_margin: float = ROUTE_MARGIN):
    """``dense_reference.gaps_to_alt``, not scored where the router
    margin of ``hid`` (this reference's) is under ``route_margin``."""
    return _scored(hid[1], dense_reference.gaps_to_alt(w, hid[0], hid_alt[0],
                                                       mode), route_margin)


@functools.partial(jax.jit, static_argnums=(0,))
def calibration_grams(d: Dims, w, tokens):
    """Gram matrices of keys, group-stacked queries and values over one
    calibration sequence, uncompressed: per kind (full, then window),
    each (layers of the kind, Hkv, d, d)."""
    with jax.default_matmul_precision("highest"):
        grams = _stack(d, w, None, tokens, "f32")[2]
    return tuple(jnp.stack([g[n] for g in grams[kind]])
                 for kind in KINDS for n in range(3))


# ---------------------------------------------------------------------------
# KQ-SVD projections (float64, on the host)
# ---------------------------------------------------------------------------


def solve_kqsvd(d: Dims, grams, wo, rank_k: int, rank_v: int) -> dict:
    """Fixed-rank KQ-SVD factors for every layer and kv head, per kind
    (the same solve as ``dense_reference.solve_kqsvd``).

    grams: the six arrays of ``calibration_grams``; wo: (L, H, dv, D).
    Returns float32 ``a_k.<kind>``, ``b_q.<kind>`` (n, Hkv, dqk, rank_k),
    ``a_v.<kind>`` (n, Hkv, dv, rank_v), ``c_v.<kind>``
    (n, Hkv, rank_v, m * D)."""
    out = {}
    for n_kind, kind in enumerate(KINDS):
        g_k, g_q, g_v = (np.asarray(g, np.float64)
                         for g in grams[3 * n_kind: 3 * n_kind + 3])
        layers = d.layers(kind)
        G, D = d.kv(kind), d.d_model
        m = d.n_heads // G
        a_k = np.zeros((len(layers), G, d.d_qk, rank_k))
        b_q = np.zeros_like(a_k)
        a_v = np.zeros((len(layers), G, d.d_v, rank_v))
        c_v = np.zeros((len(layers), G, rank_v, m * D))
        for j, l in enumerate(layers):
            wl = np.asarray(wo[l], np.float64).reshape(G, m, d.d_v, D)
            for g in range(G):
                Vk, sk = _factors(g_k[j, g])
                Vq, sq = _factors(g_q[j, g])
                U, _, _ = np.linalg.svd((sk[:, None] * (Vk.T @ Vq))
                                        * sq[None, :])
                Ur = U[:, :rank_k]
                a_k[j, g] = Vk @ (_pinv(sk)[:, None] * Ur)
                b_q[j, g] = Vk @ (sk[:, None] * Ur)
                Vv, sv = _factors(g_v[j, g])
                W = wl[g].transpose(1, 0, 2).reshape(d.d_v, m * D)
                N = sv[:, None] * (Vv.T @ W)
                e, Un = np.linalg.eigh(N @ N.T)
                Ur = Un[:, np.argsort(e)[::-1][:rank_v]]
                a_v[j, g] = Vv @ (_pinv(sv)[:, None] * Ur)
                c_v[j, g] = Ur.T @ N
        for name, arr in zip(PROJ_KEYS, (a_k, b_q, a_v, c_v)):
            out[f"{name}.{kind}"] = arr.astype(np.float32)
    out["layer_kind"] = np.asarray(d.pattern, np.float32)
    return out
