"""Granite-3.0-3B-A800M's decoder (GQA, RoPE, RMSNorm, untied head, a
routed SwiGLU expert layer in every block): the benchmark's weights and
the plain float32 forward pass with FP8 QAT, for this chip's share of the
experts.

Weights follow the program's parameter layout (``models/transformer.py``
``init_lm``: per-layer weights stacked on a leading layer axis; attention
projections with a clipping value ``<w>_qa`` of shape (L, 1, 1); the
router (L, D, 40) in float32 without one; the held experts' weights
``we_gate``/``we_up`` (L, E_here, D, F) and ``we_down`` (L, E_here, F, D)
with one clipping value per (layer, expert), (L, E_here, 1, 1);
input-activation clips ``attn_qb``, ``o_qb``, ``mlp_qb``, ``down_qb`` per
layer and ``head_qb``) and its initialisation (normal / sqrt(fan_in),
embedding and router 0.02, alpha = max|w| per clipped slab, beta = 4),
made here from the seed.

The expert layer as the published model computes it: router logits over
all experts, the top ``num_experts_per_tok``, a softmax over those; each
expert ``down(silu(gate x) * up x)``. The chip's part of it is computed
densely: every held expert is applied to every token and its output
weighted by that token's gate for it, zero where the token did not choose
it. No sort, grouping or capacity; nothing stands in for the experts held
elsewhere.

Activations and their gradients are held in ``quant.dtype`` wherever the
configuration stores them (bfloat16); arithmetic inside an op is float32.
QAT as the program applies it: every projection fake-quantizes its input
activation and its weight to E4M3, the router and the embedding lookup
are not quantized. LSQ scales each clip's gradient by the elements it
clips: one expert's weight for an expert clip; the layer's N x D input
for ``mlp_qb``; the N x top_k routed rows of the expert width for
``down_qb``; one of ``ce_chunks`` pieces of the sequence for the head's
input clip.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import flops


def _shape(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def _experts(cfg):
    """(routed experts, experts per token, held here, first held)."""
    return (cfg["router_experts"], cfg["num_experts_per_tok"],
            cfg["num_local_experts"], cfg["first_local_expert"])


def init_params(key, cfg):
    L, D, H, KV, hd, F, V = _shape(cfg)
    E, _, G, _ = _experts(cfg)
    ks = jax.random.split(key, 12)

    def w(k, shape, fan_in, lead=1):
        x = jax.random.normal(k, shape, jnp.float32) * np.sqrt(1.0 / fan_in)
        axes = tuple(range(lead, len(shape)))
        return x, jnp.max(jnp.abs(x), axis=axes, keepdims=True)

    blocks = {"ln1": jnp.ones((L, D), jnp.float32),
              "ln2": jnp.ones((L, D), jnp.float32),
              "router": jax.random.normal(ks[0], (L, D, E), jnp.float32)
              * 0.02}
    for i, (name, shape, fan, lead) in enumerate((
            ("wq", (L, D, H * hd), D, 1), ("wk", (L, D, KV * hd), D, 1),
            ("wv", (L, D, KV * hd), D, 1), ("wo", (L, H * hd, D), H * hd, 1),
            ("we_gate", (L, G, D, F), D, 2), ("we_up", (L, G, D, F), D, 2),
            ("we_down", (L, G, F, D), F, 2))):
        blocks[name], blocks[name + "_qa"] = w(ks[1 + i], shape, fan, lead)
    for site in ("attn_qb", "o_qb", "mlp_qb", "down_qb"):
        blocks[site] = jnp.full((L,), 4.0, jnp.float32)
    embed = jax.random.normal(ks[9], (V, D), jnp.float32) * 0.02
    head = jax.random.normal(ks[10], (D, V), jnp.float32) * np.sqrt(1.0 / D)
    return {"embed": embed, "embed_qa": jnp.max(jnp.abs(embed)),
            "blocks": blocks, "ln_f": jnp.ones((D,), jnp.float32),
            "lm_head": head, "lm_head_qa": jnp.max(jnp.abs(head)),
            "head_qb": jnp.asarray(4.0, jnp.float32)}


def make_loss(cfg):
    L, D, H, KV, hd, F, V = _shape(cfg)
    E, K, G, e0 = _experts(cfg)
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

    def expert_layer(p, x, q):
        """The held experts' part of the layer for tokens x (N, D)."""
        N = x.shape[0]
        logits = x @ p["router"]
        top, chosen = jax.lax.top_k(logits, K)
        gates = jax.nn.softmax(top, axis=-1)                    # (N, K)
        mine = chosen[:, :, None] == (e0 + jnp.arange(G))        # (N, K, G)
        gate = jnp.sum(gates[:, :, None] * mine, axis=1)         # (N, G)
        xq = q.act(x, p["mlp_qb"], N * D)

        def expert(name):            # each expert's weight, its own clip
            return jax.vmap(q.weight)(p[name], p[name + "_qa"])

        g = q.cast(jnp.einsum("nd,edf->enf", xq, expert("we_gate")))
        u = q.cast(jnp.einsum("nd,edf->enf", xq, expert("we_up")))
        a = q.native(lambda g, u: jax.nn.silu(g) * u, g, u)
        aq = q.act(a, p["down_qb"], N * K * F)
        y = q.cast(jnp.einsum("enf,efd->end", aq, expert("we_down")))
        return q.cast(jnp.einsum("end,ne->nd", y, gate))

    def loss(params, tokens, labels, q):
        def dense(p, name, x, site, n=None):
            xq = q.act(x, p[site], n)
            wq = q.weight(p[name], p[name + "_qa"])
            return q.cast(xq @ wq)

        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        causal = jnp.tril(jnp.ones((T, T), bool))

        # one layer at a time, its activations recomputed for the gradient
        # (the dense expert layer's would not fit the chip for four layers)
        @jax.checkpoint
        def block(h, p):
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
            h = q.cast(h + expert_layer(p, x.reshape(B * T, D), q)
                       .reshape(B, T, D))
            return h, None

        h, _ = jax.lax.scan(block, q.cast(params["embed"][tokens]),
                            params["blocks"])
        h = q.cast(rms(h, params["ln_f"]))
        logits = dense(params, "lm_head", h, "head_qb", n=h.size // chunks)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return loss


def qat_gmm_costs(rows: int, k: int, n: int, groups: int
                  ) -> dict[str, tuple[int, int]]:
    """(ops, bytes) of the grouped QAT matmul's three kernels for ``rows``
    routed (rows, k) activations against ``groups`` (k, n) expert weights:
    bf16 activations in and out, the experts' f32 master weights, their
    f32 weight gradients."""
    ops = 2 * rows * k * n
    w = groups * k * n * flops.F32
    return {
        "fwd": (ops, rows * k * flops.BF16 + w + rows * n * flops.BF16),
        "dx": (ops, rows * n * flops.BF16 + w + rows * k * flops.BF16),
        "dw": (ops, rows * k * flops.BF16 + rows * n * flops.BF16 + w),
    }


def counts(cfg, traffic, n_q: int, n_rows: int) -> dict:
    """Per-round work of the cell at the expected routed load (each held
    expert takes ``top_k / router_experts`` of the tokens): model FLOPs,
    and ``(ops, bytes, calls per round)`` of the grouped QAT matmul, of
    the dense one (attention projections and head) and of the wire's
    encode."""
    L, D, H, KV, hd, F, V = _shape(cfg)
    E, K, G, _ = _experts(cfg)
    cohort = max(1, round(traffic["clients"] * traffic["participation"]))
    steps = cohort * traffic["local_steps"]
    m = traffic["batch"] * traffic["seq"]
    load = K * G / E                   # held experts a token uses, expected
    proj = [(n, k, o, L) for n, k, o in (
        ("wq", D, H * hd), ("wk", D, KV * hd), ("wv", D, KV * hd),
        ("wo", H * hd, D), ("router", D, E))]
    proj += [(n, k, o, L * load) for n, k, o in (
        ("we_gate", D, F), ("we_up", D, F), ("we_down", F, D))]
    proj.append(("lm_head", D, V, 1))
    per_token = flops.lm_flops_per_token(proj, L, H, hd, traffic["seq"])
    rows = round(m * load)
    gmm = [(*cost, L * steps)
           for k, n in ((D, F), (D, F), (F, D))
           for cost in qat_gmm_costs(rows, k, n, G).values()]
    mm = [(*cost, count * steps)
          for name, k, n, count in proj
          if name in ("wq", "wk", "wv", "wo", "lm_head")
          for cost in flops.qat_matmul_costs(m, k, n).values()]
    return {"model_flops": per_token * m * steps, "qat_gmm": gmm,
            "qat_matmul": mm,
            "quant_pack": [(*flops.quant_pack_cost(n_q, n_rows), 1 + cohort)]}
