"""Granite-3.0-8B's decoder (GQA, SwiGLU, RoPE, RMSNorm, untied head):
the benchmark's weights and the plain float32 forward pass with FP8 QAT.

Weights follow the program's parameter layout (``models/transformer.py``
``init_lm``: per-layer weights stacked on a leading layer axis, each with
its clipping value ``<w>_qa`` of shape (L, 1, 1); input-activation clips
``attn_qb``, ``o_qb``, ``mlp_qb``, ``down_qb`` per layer and ``head_qb``)
and its initialisation (normal / sqrt(fan_in), embedding 0.02,
alpha = max|w| per layer, beta = 4), made here from the seed.

Activations and their gradients are held in ``quant.dtype`` wherever the
configuration stores them (bfloat16): the embedding lookup, every norm's,
projection's, rotary and attention output, and the residual stream;
arithmetic inside an op is float32.

QAT as the program applies it: every projection fake-quantizes its input
activation and its weight to E4M3; the embedding lookup is not quantized.
The head's input clip is LSQ-scaled over one of ``ce_chunks`` pieces of
the sequence, the piece the program quantizes at once.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import flops


def _shape(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def init_params(key, cfg):
    L, D, H, KV, hd, F, V = _shape(cfg)
    ks = jax.random.split(key, 10)

    def w(k, shape, fan_in):
        x = jax.random.normal(k, shape, jnp.float32) * np.sqrt(1.0 / fan_in)
        return x, jnp.max(jnp.abs(x), axis=(1, 2), keepdims=True)

    blocks = {"ln1": jnp.ones((L, D), jnp.float32),
              "ln2": jnp.ones((L, D), jnp.float32)}
    for i, (name, shape, fan) in enumerate((
            ("wq", (L, D, H * hd), D), ("wk", (L, D, KV * hd), D),
            ("wv", (L, D, KV * hd), D), ("wo", (L, H * hd, D), H * hd),
            ("w_gate", (L, D, F), D), ("w_up", (L, D, F), D),
            ("w_down", (L, F, D), F))):
        blocks[name], blocks[name + "_qa"] = w(ks[i], shape, fan)
    for site in ("attn_qb", "o_qb", "mlp_qb", "down_qb"):
        blocks[site] = jnp.full((L,), 4.0, jnp.float32)
    embed = jax.random.normal(ks[7], (V, D), jnp.float32) * 0.02
    head = jax.random.normal(ks[8], (D, V), jnp.float32) * np.sqrt(1.0 / D)
    return {"embed": embed, "embed_qa": jnp.max(jnp.abs(embed)),
            "blocks": blocks, "ln_f": jnp.ones((D,), jnp.float32),
            "lm_head": head, "lm_head_qa": jnp.max(jnp.abs(head)),
            "head_qb": jnp.asarray(4.0, jnp.float32)}


def make_loss(cfg):
    L, D, H, KV, hd, F, V = _shape(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    chunks = cfg["ce_chunks"]

    def rms(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + eps)) * scale

    def rope(x, pos):
        half = x.shape[-1] // 2
        freqs = jnp.exp(-np.log(theta)
                        * jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = (pos[..., :, None].astype(jnp.float32) * freqs)[..., None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def loss(params, tokens, labels, q):
        def dense(p, name, x, site, n=None):
            xq = q.act(x, p[site], n)
            wq = q.weight(p[name], p[name + "_qa"])
            return q.cast(xq @ wq)

        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        causal = jnp.tril(jnp.ones((T, T), bool))
        h = q.cast(params["embed"][tokens])
        for layer in range(L):
            p = jax.tree.map(lambda a: a[layer], params["blocks"])
            x = q.cast(rms(h, p["ln1"]))
            qh = q.cast(rope(dense(p, "wq", x, "attn_qb").reshape(B, T, H, hd),
                             pos))
            kh = q.cast(rope(dense(p, "wk", x, "attn_qb").reshape(B, T, KV, hd),
                             pos))
            vh = dense(p, "wv", x, "attn_qb").reshape(B, T, KV, hd)
            kh = jnp.repeat(kh, H // KV, axis=2)
            vh = jnp.repeat(vh, H // KV, axis=2)
            s = jnp.einsum("bthd,bshd->bhts", qh * (1.0 / np.sqrt(hd)), kh)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = q.cast(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vh))
            h = q.cast(h + dense(p, "wo", a.reshape(B, T, H * hd), "o_qb"))
            x = q.cast(rms(h, p["ln2"]))
            g = dense(p, "w_gate", x, "mlp_qb")
            u = dense(p, "w_up", x, "mlp_qb")
            a = q.native(lambda g, u: jax.nn.silu(g) * u, g, u)
            h = q.cast(h + dense(p, "w_down", a, "down_qb"))
        h = q.cast(rms(h, params["ln_f"]))
        logits = dense(params, "lm_head", h, "head_qb", n=h.size // chunks)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return loss


def counts(cfg, traffic, n_q: int, n_rows: int) -> dict:
    """Per-round work of the cell: model FLOPs, and ``(ops, bytes, calls
    per round)`` of the kernels whose rooflines are read."""
    L, D, H, KV, hd, F, V = _shape(cfg)
    cohort = max(1, round(traffic["clients"] * traffic["participation"]))
    steps = cohort * traffic["local_steps"]
    m = traffic["batch"] * traffic["seq"]
    proj = flops.lm_projections(D, H, KV, hd, F, V, L)
    per_token = flops.lm_flops_per_token(proj, L, H, hd, traffic["seq"])
    mm = [(*cost, count * steps)
          for _, k, n, count in proj
          for cost in flops.qat_matmul_costs(m, k, n).values()]
    return {"model_flops": per_token * m * steps,
            "qat_matmul": mm,
            "quant_pack": [(*flops.quant_pack_cost(n_q, n_rows), 1 + cohort)]}
