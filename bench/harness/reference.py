"""Plain float32 reference of what the federation serves.

Written from the published architectures and the paper's fuser, in plain
``jax.numpy`` at float32 with the highest matmul precision: no kernels, no
pages, no cache, no batching. It imports nothing of the program and reads
only the configuration file and the benchmark's own weights.

- Decoder: the embedding, then each layer's ``block`` from the model's
  architecture (``bench/ref/<model_type>.py``), then a final RMSNorm and the
  logits on the output head: the embedding where ``tie_word_embeddings``,
  else ``lm_head``. A block gets ``attend``, the attention every
  architecture here shares: grouped-query attention with rotary embeddings
  (half-split pairs, ``rope_theta``) on q and k, per-head RMSNorm of q and k
  before the rotation where ``qk_norm`` (Qwen3), q/k/v biases where the
  weights hold them (Qwen2), over the fused prefix and the causal keys.
- C2C (the paper's Eq. 1 and 4): the transmitter prefills the prompt; for
  each receiver layer r, the transmitter layer min(r, L_tx - 1) (bottom-up
  alignment) has each position's k and v rows (heads flattened, k then v)
  mapped by that layer's three-layer MLP (SiLU, SiLU, linear) to receiver k
  and v rows. The fused keys are not rotated again. Each fused position's
  attention logit gets the bias log sigmoid(gate_r) + log w, where w is the
  gating network's weight: sigmoid(w2 . tanh(w1 . mean(fused k||v))) over
  every layer and position. The receiver attends over the fused prefix and
  its own causal keys in one softmax.

``lower=True`` computes the same in the next precision below the served
bf16: every operand of every matrix product rounded to nearest at three
mantissa bits, fp8 e4m3's precision (on the bits, since a compiler may drop
a round trip through a narrower type). That is the control of the check.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MASK = -1e30
BLOCK = 256  # positions per logits block


def round_fp8(a: jax.Array) -> jax.Array:
    """float32 ``a`` rounded to nearest at 3 mantissa bits."""
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _f(a, lower: bool):
    a = a.astype(jnp.float32)
    return round_fp8(a) if lower else a


def mm(x, w, lower: bool):
    """``x @ w`` on float32 operands at the highest precision (rounded to
    fp8's precision first, where ``lower``)."""
    return jnp.matmul(_f(x, lower), _f(w, lower), precision=HIGHEST)


def rms_norm(x, scale, eps):
    """RMSNorm over the last axis, scaled by ``scale``."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta: float):
    """Rotate (H, S, hd) at positions 0..S-1, half-split pairs."""
    _, S, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(lp, name, h, lower):
    y = mm(h, lp[name]["w"], lower)
    if "b" in lp[name]:
        y = y + lp[name]["b"].astype(jnp.float32)
    return y


def attention(hf: Dict, a: Dict, h: jax.Array, pre: Optional[tuple],
              causal: jax.Array, lower: bool):
    """One layer's attention of the normed ``h`` (W, d) with its weights
    ``a``, over this layer's fused prefix ``pre`` = (k, v, bias), if any,
    and its own causal keys. Returns (the output projection (W, d), (k, v)
    rotated, (Hkv, W, hd) each)."""
    W = h.shape[0]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // H
    G = H // Hkv
    eps = hf["rms_norm_eps"]
    scale = hd ** -0.5
    q = _proj(a, "wq", h, lower).reshape(W, H, hd)
    k = _proj(a, "wk", h, lower).reshape(W, Hkv, hd)
    v = _proj(a, "wv", h, lower).reshape(W, Hkv, hd)
    if hf["qk_norm"]:
        q = rms_norm(q, a["q_norm"], eps)
        k = rms_norm(k, a["k_norm"], eps)
    q = _rope(q.transpose(1, 0, 2), hf["rope_theta"])
    k = _rope(k.transpose(1, 0, 2), hf["rope_theta"])
    v = v.transpose(1, 0, 2)
    qg = _f(q, lower).reshape(Hkv, G, W, hd)
    s = jnp.einsum("kgqd,ktd->kgqt", qg, _f(k, lower),
                   precision=HIGHEST) * scale
    s = jnp.where(causal, s, MASK)
    vals = v
    if pre is not None:
        kp, vp, bp = pre
        sp = jnp.einsum("kgqd,ktd->kgqt", qg, _f(kp, lower),
                        precision=HIGHEST) * scale + bp
        s = jnp.concatenate([sp, s], axis=-1)
        vals = jnp.concatenate([vp.astype(jnp.float32), v], axis=1)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,ktd->kgqd", _f(p, lower), _f(vals, lower),
                   precision=HIGHEST)
    o = o.reshape(H, W, hd).transpose(1, 0, 2).reshape(W, H * hd)
    return mm(o, a["wo"]["w"], lower), (k, v)


def decoder(hf: Dict, block: Callable, params: Dict, tokens: jax.Array,
            prefix: Optional[tuple] = None, *, lower: bool = False,
            keep_kv: bool = False):
    """Causal forward over ``tokens`` (W,), each layer by ``block(hf,
    layer_params, x, attend, lower) -> (x, (k, v))``, where ``attend(a, h)``
    is ``attention`` over the layer's prefix. ``prefix`` = (k, v, bias) with
    k/v (L, Hkv, P, hd) and bias (L, P): fused keys every query sees.
    Returns (final normed hidden states (W, d), per-layer rotated k and v
    (L, Hkv, W, hd) each, if ``keep_kv``)."""
    W = tokens.shape[0]
    causal = jnp.tril(jnp.ones((W, W), bool))
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    stack = params["cycle"][0]

    def body(x, xs):
        lp, pre = xs
        attend = functools.partial(attention, hf, pre=pre, causal=causal,
                                   lower=lower)
        x, kv = block(hf, lp, x, attend, lower)
        return x, (kv if keep_kv else None)

    x, kv = jax.lax.scan(body, x, (stack, prefix))
    x = rms_norm(x, params["final_norm"]["scale"], hf["rms_norm_eps"])
    return x, kv


def fused_prefix(tx_hf: Dict, rx_hf: Dict, tx_block: Callable,
                 tx_params: Dict, fuser: Dict, gate: Dict, tokens: jax.Array,
                 n: jax.Array, *, lower: bool = False):
    """The transmit side of one C2C request: (k, v, bias) over the
    transmitter prompt ``tokens`` (Wt,), of which the first ``n`` are real
    (the rest are masked out); the transmitter's layers are ``tx_block``."""
    _, (k, v) = decoder(tx_hf, tx_block, tx_params, tokens, lower=lower,
                        keep_kv=True)
    L_rx, L_tx = rx_hf["num_hidden_layers"], tx_hf["num_hidden_layers"]
    align = np.minimum(np.arange(L_rx), L_tx - 1)
    Wt = tokens.shape[0]

    def rows(a):  # (L, Hkv, Wt, hd) -> (L, Wt, Hkv * hd)
        return a[align].transpose(0, 2, 1, 3).reshape(L_rx, Wt, -1)

    x = jnp.concatenate([rows(k), rows(v)], axis=-1)
    m = fuser["mlp"]

    def lin(p, x):
        return (jnp.einsum("lsi,lio->lso", _f(x, lower), _f(p["w"], lower),
                           precision=HIGHEST)
                + p["b"].astype(jnp.float32)[:, None])

    y = lin(m["w3"], jax.nn.silu(lin(m["w2"], jax.nn.silu(lin(m["w1"], x)))))
    Hr = rx_hf["num_key_value_heads"]
    hdr = rx_hf.get("head_dim") or rx_hf["hidden_size"] // \
        rx_hf["num_attention_heads"]
    kh, vh = jnp.split(y, 2, axis=-1)  # (L, Wt, Hr * hdr) each
    live = (jnp.arange(Wt) < n).astype(jnp.float32)
    feat = (jnp.concatenate([kh, vh], -1) * live[None, :, None]).sum((0, 1))
    feat = feat / (L_rx * n)
    g1 = jnp.tanh(mm(feat, gate["w1"]["w"], lower)
                  + gate["w1"]["b"].astype(jnp.float32))
    w = jax.nn.sigmoid(mm(g1, gate["w2"]["w"], lower)
                       + gate["w2"]["b"].astype(jnp.float32))[0]
    layer_gate = -jax.nn.softplus(-fuser["gate"].astype(jnp.float32))
    bias = layer_gate[:, None] + jnp.log(jnp.maximum(w, 1e-30))
    bias = jnp.where(live[None] > 0, bias, MASK)

    def heads(a):  # (L, Wt, Hr * hdr) -> (L, Hr, Wt, hdr)
        return a.reshape(L_rx, Wt, Hr, hdr).transpose(0, 2, 1, 3)

    return heads(kh), heads(vh), bias


def logit_stats(table: jax.Array, h: jax.Array, picks: jax.Array, *,
                lower: bool = False):
    """Per position of ``h`` (W, d): the largest logit, the logits' RMS, the
    logit of each token in ``picks`` (m, W) and the argmax, in blocks of
    positions so that (W, vocab) logits never exist at once."""
    W = h.shape[0]
    hb = h.reshape(W // BLOCK, BLOCK, -1)
    pb = picks.reshape(picks.shape[0], W // BLOCK, BLOCK).transpose(1, 0, 2)
    t = _f(table, lower)

    def block(_, xs):
        hh, pp = xs
        lg = jnp.matmul(_f(hh, lower), t.T, precision=HIGHEST)
        got = jnp.take_along_axis(lg[None], jnp.maximum(pp, 0)[..., None],
                                  axis=-1)[..., 0]
        return None, (lg.max(-1), jnp.sqrt(jnp.mean(lg * lg, -1)), got,
                      jnp.argmax(lg, -1).astype(jnp.int32))

    _, (mx, rms, got, arg) = jax.lax.scan(block, None, (hb, pb))
    return (mx.reshape(W), rms.reshape(W),
            got.transpose(1, 0, 2).reshape(picks.shape[0], W),
            arg.reshape(W))


def head_table(hf: Dict, params: Dict) -> jax.Array:
    """The output head as a (vocab, d) table: the embedding where the model
    ties them, else the transpose of ``lm_head``'s (d, vocab) weight."""
    if hf["tie_word_embeddings"]:
        return params["embed"]["table"]
    return params["lm_head"]["w"].T


def head_stats(hf: Dict, params: Dict, h: jax.Array, picks: jax.Array, *,
               lower: bool = False):
    """``logit_stats`` on the model's own output head."""
    return logit_stats(head_table(hf, params), h, picks, lower=lower)


class Reference:
    """The reference of one configuration, over the benchmark's weights.
    Every sequence is right-padded to ``width`` tokens and every transmitter
    prompt to ``c2c_width`` (causal: the rows read stay exact), so one
    program serves every request; a standalone request attends an
    all-masked prefix, which adds exactly nothing. ``archs``: the reference
    sides (``bench/ref/<model_type>.py``) of the receiver and the
    transmitter."""

    def __init__(self, config: Dict, weights: Dict, width: int,
                 c2c_width: int, archs):
        self.rx_hf, self.tx_hf = config["receiver"], config["transmitter"]
        rx_arch, tx_arch = archs
        self.w = weights
        self.width = -(-width // 512) * 512
        self.c2c_width = c2c_width
        self._dec = jax.jit(functools.partial(decoder, self.rx_hf,
                                              rx_arch.block),
                            static_argnames=("lower",))
        self._pre = jax.jit(functools.partial(fused_prefix, self.tx_hf,
                                              self.rx_hf, tx_arch.block),
                            static_argnames=("lower",))
        self._stats = jax.jit(functools.partial(head_stats, self.rx_hf),
                              static_argnames=("lower",))

    def _masked_prefix(self):
        hf = self.rx_hf
        H = hf["num_attention_heads"]
        hd = hf.get("head_dim") or hf["hidden_size"] // H
        shape = (hf["num_hidden_layers"], hf["num_key_value_heads"],
                 self.c2c_width, hd)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
                jnp.full(shape[:1] + shape[2:3], MASK, jnp.float32))

    def _run(self, prompt, served, c2c: bool, lower: bool, picks):
        S, n = len(prompt), len(served)
        W = self.width
        if S + n - 1 > W or (c2c and S > self.c2c_width):
            raise ValueError(f"request of {S} + {n} tokens exceeds the "
                             f"reference's width {W}")
        seq = np.zeros(W, np.int32)
        seq[:S] = prompt
        seq[S:S + n - 1] = served[:-1]
        if c2c:
            tx = np.zeros(self.c2c_width, np.int32)
            tx[:S] = prompt
            pre = self._pre(self.w["tx"], self.w["fuser"], self.w["gate"],
                            jnp.asarray(tx), jnp.int32(S), lower=lower)
        else:
            pre = self._masked_prefix()
        h, _ = self._dec(self.w["rx"], jnp.asarray(seq), pre, lower=lower)
        pk = np.full((picks.shape[0], W), -1, np.int32)
        pk[:, S - 1:S - 1 + n] = picks
        return self._stats(self.w["rx"], h, jnp.asarray(pk),
                           lower=lower), S, n

    def gaps(self, prompt: np.ndarray, served: np.ndarray, c2c: bool,
             control: bool = False) -> Dict[str, np.ndarray]:
        """How far below the reference's best logit each served token lies,
        in units of the RMS of the reference's logits at that position
        (teacher-forced over the prompt and the served tokens). With
        ``control``, also the gap of the token the lower precision puts
        first at each of those positions."""
        picks = np.asarray(served, np.int32)[None]
        if control:
            (_, _, _, arg), S, n = self._run(prompt, served, c2c, True,
                                             picks)
            picks = np.stack([picks[0], np.asarray(arg)[S - 1:S - 1 + n]])
        (mx, rms, got, _), S, n = self._run(prompt, served, c2c, False,
                                            picks)
        sl = slice(S - 1, S - 1 + n)
        mx, rms, got = (np.asarray(mx, np.float64)[sl],
                        np.asarray(rms, np.float64)[sl],
                        np.asarray(got, np.float64)[:, sl])
        out = {"served": (mx - got[0]) / rms}
        if control:
            out["control"] = (mx - got[1]) / rms
        return out
