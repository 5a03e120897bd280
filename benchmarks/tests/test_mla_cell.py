"""The Moonlight cell's pieces that need a JAX backend in this process, so
they sort behind every test that calls ``cli.main`` (``test_arch_driver*``,
``test_end_to_end.py``): the blockwise comparison against
``serve_arch_common.routed_facts``, and the ``test_reference`` case of
``deepseek_v3_arch``: the reference against a literal per-token loop."""

import numpy as np
import pytest


def test_blockwise_facts_are_routed_facts():
    """The same numbers as ``serve_arch_common.routed_facts`` computes from
    whole arrays, with blocks that do not divide the positions."""
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import serve_arch_common, serve_closed_loop_arch_blockwise as bw
    from benchmarks.reference import deepseek_v3_arch as arch

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    hidden = jax.random.normal(keys[0], (23, 16))
    params = {"final_norm": 1 + 0.1 * jax.random.normal(keys[1], (16,)),
              "lm_head": jax.random.normal(keys[2], (16, 40)).astype(jnp.bfloat16)}
    ref = arch.head(hidden[None], params["final_norm"], params["lm_head"], eps=1e-5)[0]
    eng = (ref + 0.01 * jax.random.normal(keys[3], ref.shape)).astype(jnp.bfloat16)
    slack = [jnp.zeros(23), jnp.zeros(23).at[4].set(0.02)]
    want = serve_arch_common.routed_facts(ref, slack, eng)
    old = bw.POSITION_BLOCK
    bw.POSITION_BLOCK = 8
    try:
        got = bw.blockwise_facts(arch, params, hidden, eng, 1e-5)
    finally:
        bw.POSITION_BLOCK = old
    assert got["positions"] == want["positions"] == 23 and got["finite"]
    assert got["max_abs_logit_diff"] == pytest.approx(want["max_abs_logit_diff"], rel=1e-6)
    assert got["rms_logit_diff"] == pytest.approx(want["rms_logit_diff"], rel=1e-5)
    # the head in blocks of the vocabulary is the head at once
    old = arch.VOCAB_BLOCK
    arch.VOCAB_BLOCK = 8
    try:
        arch.head.clear_cache() if hasattr(arch.head, "clear_cache") else None
        blocked = arch.head(hidden[None], params["final_norm"], params["lm_head"], eps=1e-5)[0]
    finally:
        arch.VOCAB_BLOCK = old
    assert float(jnp.max(jnp.abs(blocked - ref))) < 1e-5


def _literal(params, tokens, *, n_layers, n_heads, rank, nope, rope_dim, theta, eps,
             top_k, norm_topk_prob, scale):
    """``modeling_deepseek.py`` a token at a time in float64 numpy: no
    batch, no matrices of positions, the experts a token chose and no
    others, keys and values up-projected from each position's latent."""
    p = jax_to_numpy(params)

    def norm(x, w):
        return x / np.sqrt(np.mean(x * x) + eps) * w

    def rotate(x, pos):  # (..., d)
        d = x.shape[-1]
        inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
        ang = np.concatenate([pos * inv, pos * inv])
        half = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
        return x * np.cos(ang) + half * np.sin(ang)

    def swiglu(h, gate, up, down):
        a = h @ gate
        return (a / (1 + np.exp(-a)) * (h @ up)) @ down

    x = [p["embed"][t] for t in tokens]
    for i in range(n_layers):
        w = p[f"layer_{i}"]
        attn = w["attn"]
        q, k, v = [], [], []
        for pos, xt in enumerate(x):
            h = norm(xt, w["attn_norm"])
            qt = (h @ attn["wq"]["kernel"]).reshape(n_heads, nope + rope_dim)
            q.append(np.concatenate([qt[:, :nope], rotate(qt[:, nope:], pos)], axis=-1))
            kva = h @ attn["wkv_a"]["kernel"]
            c = norm(kva[:rank], attn["kv_norm"])
            k_rope = rotate(kva[rank:], pos)
            kv = np.einsum("r,rhd->hd", c, attn["wkv_b"])
            k.append(np.concatenate(
                [kv[:, :nope], np.tile(k_rope, (n_heads, 1))], axis=-1))
            v.append(kv[:, nope:])
        for pos in range(len(x)):
            out = []
            for head in range(n_heads):
                scores = np.array([q[pos][head] @ k[j][head] for j in range(pos + 1)])
                scores = scores / np.sqrt(nope + rope_dim)
                probs = np.exp(scores - scores.max())
                probs /= probs.sum()
                out.append(sum(pj * v[j][head] for j, pj in enumerate(probs)))
            x[pos] = x[pos] + np.concatenate(out) @ attn["wo"]["kernel"]
        for pos, xt in enumerate(x):
            h = norm(xt, w["ffn_norm"])
            if "moe" not in w:
                mlp = w["mlp"]
                x[pos] = xt + swiglu(h, mlp["w_gate"]["kernel"], mlp["w_up"]["kernel"],
                                     mlp["w_down"]["kernel"])
                continue
            moe, shared = w["moe"], w["shared"]
            scores = 1 / (1 + np.exp(-(h @ moe["router"])))
            chosen = np.argsort(-(scores + moe["router_bias"]), kind="stable")[:top_k]
            kept = scores[chosen]
            if norm_topk_prob:
                kept = kept / (kept.sum() + 1e-20)
            y = sum(wj * scale * swiglu(h, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
                    for wj, e in zip(kept, chosen))
            x[pos] = xt + y + swiglu(h, shared["w_gate"]["kernel"], shared["w_up"]["kernel"],
                                     shared["w_down"]["kernel"])
    return np.stack([norm(xt, p["final_norm"]) @ p["lm_head"] for xt in x])


def jax_to_numpy(tree):
    import jax

    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def _weights(seed=0, dim=16, heads=2, rank=8, nope=4, rope=4, dv=4, dense=24, inner=8,
             experts=6, vocab=32, layers=3):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 128))

    def normal(*shape, scale=0.3):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    def swiglu(width):
        return {"w_gate": {"kernel": normal(dim, width)}, "w_up": {"kernel": normal(dim, width)},
                "w_down": {"kernel": normal(width, dim)}}

    def layer(routed):
        blk = {
            "attn_norm": 1 + normal(dim), "ffn_norm": 1 + normal(dim),
            "attn": {"wq": {"kernel": normal(dim, heads * (nope + rope))},
                     "wkv_a": {"kernel": normal(dim, rank + rope)},
                     "kv_norm": 1 + normal(rank), "wkv_b": normal(rank, heads, nope + dv),
                     "wo": {"kernel": normal(heads * dv, dim)}}}
        if not routed:
            return dict(blk, mlp=swiglu(dense))
        return dict(blk, shared=swiglu(2 * inner), moe={
            "router": normal(dim, experts, scale=1.0), "router_bias": normal(experts, scale=0.2),
            "w_gate": normal(experts, dim, inner), "w_up": normal(experts, dim, inner),
            "w_down": normal(experts, inner, dim)})

    params = {f"layer_{i}": layer(i > 0) for i in range(layers)}
    params.update(embed=normal(vocab, dim, scale=1.0), final_norm=1 + normal(dim),
                  lm_head=normal(dim, vocab))
    return params


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_reference_is_the_published_description_a_token_at_a_time(norm_topk_prob):
    """The ``test_reference`` case of ``deepseek_v3_arch``: against the
    literal loop, and causal."""
    import jax.numpy as jnp

    from benchmarks.reference import deepseek_v3_arch as arch

    sizes = dict(n_layers=3, n_heads=2, rank=8, nope=4, rope_dim=4, theta=50000.0,
                 eps=1e-5, top_k=2, norm_topk_prob=norm_topk_prob, scale=2.446)
    params = _weights()
    tokens = [3, 17, 5, 9, 30, 2, 11]
    routing = []
    got = np.asarray(arch.logits(
        params, jnp.asarray([tokens], jnp.int32), routing=routing, **sizes)[0])
    want = _literal(params, tokens, **sizes)
    assert len(routing) == 2  # the dense first layer routes nothing
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    changed = tokens[:-1] + [(tokens[-1] + 1) % 32]
    again = np.asarray(arch.logits(params, jnp.asarray([changed], jnp.int32), **sizes)[0])
    assert np.abs(again[:-1] - got[:-1]).max() < 1e-6
    last = np.asarray(arch.logits(params, jnp.asarray([tokens], jnp.int32), last=3, **sizes)[0])
    assert np.abs(last - got[-3:]).max() < 1e-5
