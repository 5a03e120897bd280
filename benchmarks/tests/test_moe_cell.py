"""What the OLMoE cell added to the benchmark, rehearsed on the CPU: how it
entered ``BENCHMARK.json``, the reference against a literal per-token loop,
and each new reader on a hand-built result. The driver kind itself is
``test_arch_driver.py`` (which has to run before anything initialises a JAX
backend in this process)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.conftest import TINY_MOE_CELL as CELL
from benchmarks.harness import cli, flops_moe, manifest, xplane_scopes
from benchmarks.reference import olmoe_arch

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = [
    ("moe_experts_busy_share", "%", "device_trace", "jitted program"),
    ("moe_experts_roofline", "%", "device_trace", "kernel"),
    ("moe_decode_roofline", "%", "device_trace", "kernel"),
    ("moe_experts_touched_mean", "experts", "program_counter", "expert layer"),
    ("moe_expert_load_max_over_mean", "ratio", "program_counter", "expert layer"),
]


def test_the_real_cell_entered_only_by_additions(tiny_moe_benchmark):
    names = [m["name"] for m in tiny_moe_benchmark["per_layer"]
             if m.get("workloads") == [CELL]]
    assert names == [name for name, *_ in NEW_METRICS]
    assert {m["name"] for m in manifest.metrics_of(CELL, "end_to_end")} == {
        "out_tok_per_s", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"] for m in manifest.metrics_of(CELL, "per_layer")}
    assert "decode_roofline" not in per_layer  # its bytes are dense
    assert {"decode_step_device_ms", "kv_copy_busy_share",
            "engine_decode_batch_mean"} <= per_layer
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    cell = next(w for w in real["workloads"] if w["name"] == "olmoe-chat-backlog")
    config = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", cell["config"] + ".json"))
    mix = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    assert mix["kind"] == "serve_closed_loop_arch"
    assert config["architecture"] == "olmoe_arch"
    assert config["reduced"] == ["num_hidden_layers"]
    arguments = olmoe_arch.llm_arguments(config)
    assert arguments["model_family"] == "moe"
    assert arguments["model_kwargs"]["n_experts"] == 64
    assert arguments["model_kwargs"]["experts_per_token"] == 8
    assert arguments["model_kwargs"]["intermediate"] == 1024
    assert arguments["model_kwargs"]["norm_topk_prob"] is False


def _weights(seed=0, dim=16, inner=8, experts=4, heads=2, vocab=32, layers=2):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def normal(*shape, scale=0.3):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    def layer():
        proj = lambda: {"base": {"kernel": normal(dim, dim)}}  # noqa: E731
        return {
            "attn_norm": 1 + normal(dim), "ffn_norm": 1 + normal(dim),
            "attn": {"wq": proj(), "wk": proj(), "wv": proj(), "wo": proj(),
                     "q_norm": 1 + normal(dim), "k_norm": 1 + normal(dim)},
            "moe": {"router": normal(dim, experts, scale=1.0),
                    "w_gate": normal(experts, dim, inner),
                    "w_up": normal(experts, dim, inner),
                    "w_down": normal(experts, inner, dim)},
        }

    params = {f"layer_{i}": layer() for i in range(layers)}
    params.update(embed=normal(vocab, dim, scale=1.0), final_norm=1 + normal(dim),
                  lm_head=normal(dim, vocab))
    return params


def _literal(params, tokens, *, n_layers, n_heads, theta, eps, top_k, norm_topk_prob):
    """``modeling_olmoe.py`` a token at a time in float64 numpy: no batch,
    no matrices of positions, the experts a token chose and no others."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)

    def norm(x, w):
        return x / np.sqrt(np.mean(x * x) + eps) * w

    def rotate(x, pos):  # (heads, d)
        d = x.shape[-1]
        inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
        ang = np.concatenate([pos * inv, pos * inv])
        half = np.concatenate([-x[:, d // 2:], x[:, :d // 2]], axis=-1)
        return x * np.cos(ang) + half * np.sin(ang)

    x = [p["embed"][t] for t in tokens]
    for i in range(n_layers):
        w = p[f"layer_{i}"]
        attn, moe = w["attn"], w["moe"]
        q, k, v = [], [], []
        for pos, xt in enumerate(x):
            h = norm(xt, w["attn_norm"])
            qt = norm(h @ attn["wq"]["base"]["kernel"], attn["q_norm"])
            kt = norm(h @ attn["wk"]["base"]["kernel"], attn["k_norm"])
            q.append(rotate(qt.reshape(n_heads, -1), pos))
            k.append(rotate(kt.reshape(n_heads, -1), pos))
            v.append((h @ attn["wv"]["base"]["kernel"]).reshape(n_heads, -1))
        for pos in range(len(x)):
            out = []
            for head in range(n_heads):
                scores = np.array([
                    q[pos][head] @ k[j][head] for j in range(pos + 1)
                ]) / np.sqrt(q[pos].shape[-1])
                probs = np.exp(scores - scores.max())
                probs /= probs.sum()
                out.append(sum(pj * v[j][head] for j, pj in enumerate(probs)))
            x[pos] = x[pos] + np.concatenate(out) @ attn["wo"]["base"]["kernel"]
        for pos, xt in enumerate(x):
            h = norm(xt, w["ffn_norm"])
            logits = h @ moe["router"]
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            chosen = np.argsort(-probs, kind="stable")[:top_k]
            kept = probs[chosen] / (probs[chosen].sum() if norm_topk_prob else 1.0)
            y = 0.0
            for e, pe in zip(chosen, kept):
                gate = h @ moe["w_gate"][e]
                y = y + pe * ((gate / (1 + np.exp(-gate)) * (h @ moe["w_up"][e]))
                              @ moe["w_down"][e])
            x[pos] = xt + y
    return np.stack([norm(xt, p["final_norm"]) @ p["lm_head"] for xt in x])


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_reference_against_a_literal_per_token_loop(norm_topk_prob):
    params = _weights()
    tokens = [3, 17, 9, 30, 1, 22, 9]
    sizes = dict(n_layers=2, n_heads=2, n_kv_heads=2, theta=10000.0, eps=1e-5,
                 top_k=2, norm_topk_prob=norm_topk_prob)
    got = olmoe_arch.logits(params, jnp.asarray([tokens], jnp.int32), **sizes)[0]
    literal = dict(sizes)
    literal.pop("n_kv_heads")
    want = _literal(params, tokens, **literal)
    # float32 under "highest" against float64: rounding alone
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    last = olmoe_arch.logits(
        params, jnp.asarray([tokens], jnp.int32), last=3, **sizes)[0]
    np.testing.assert_allclose(np.asarray(last), np.asarray(got[-3:]), atol=1e-6)


def test_the_reference_follows_a_routing_and_says_how_fair_it_was():
    h = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 0.5]])
    router = jnp.asarray([[3.0, 2.0, 1.0, 0.0], [0.0, 1.0, 2.0, 2.5]])
    kept, experts, own, slack = olmoe_arch.route(h, router, 2, False)
    assert own.tolist() == [[0, 1], [3, 2], [0, 1]] and experts is own
    assert slack.tolist() == [0.0, 0.0, 0.0]
    probs = np.asarray(jax.nn.softmax(h @ router, axis=-1))
    # the same sets in another order; then rows 0 and 2 take their third
    # choice for their second
    for follow, want in (([[1, 0], [2, 3], [1, 0]], [0.0, 0.0, 0.0]),
                         ([[0, 2], [3, 2], [0, 2]],
                          [1 - probs[0, 2] / probs[0, 1], 0.0, 1 - probs[2, 2] / probs[2, 1]])):
        follow = jnp.asarray(follow)
        kept, experts, again, slack = olmoe_arch.route(h, router, 2, False, follow)
        assert experts is follow and again.tolist() == own.tolist()
        np.testing.assert_allclose(np.asarray(slack), want, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(kept), np.take_along_axis(probs, np.asarray(follow), -1), atol=1e-6)
    kept, *_ = olmoe_arch.route(h, router, 2, True, follow)
    np.testing.assert_allclose(np.asarray(kept.sum(-1)), 1.0, atol=1e-6)
    # through the whole pass: following its own choice changes nothing,
    # following another moves the logits and shows in the slack
    params = _weights()
    sizes = dict(n_layers=2, n_heads=2, n_kv_heads=2, theta=10000.0, eps=1e-5,
                 top_k=2, norm_topk_prob=False)
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    routing, slack = [], []
    plain = olmoe_arch.logits(params, tokens, routing=routing, **sizes)
    same = olmoe_arch.logits(params, tokens, follow=routing, slack=slack, **sizes)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(same))
    assert len(slack) == sizes["n_layers"] and not any(float(s.max()) for s in slack)
    other = [(r + 1) % 4 for r in routing]
    slack = []
    moved = olmoe_arch.logits(params, tokens, follow=other, slack=slack, **sizes)
    assert float(jnp.max(jnp.abs(moved - plain))) > 1e-3
    assert max(float(s.max()) for s in slack) > 0


def test_the_routed_check_on_hand_built_logits():
    from benchmarks.drivers.serve_arch_common import routed_facts, within

    slack = [jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.02]), jnp.asarray([0.0, 0.05, 0.0, 0.0, 0.0])]
    ref_all = jnp.zeros((5, 4)).at[:, 0].set(1.0)
    off = jnp.asarray([0.01, 0.04, 0.03, -0.02, 0.0])
    eng_all = ref_all.at[jnp.arange(5), jnp.asarray([1, 2, 3, 1, 2])].add(off)
    facts = routed_facts(ref_all, slack, eng_all.astype(jnp.bfloat16))
    assert facts["positions"] == 5
    assert facts["routing_agree_share"] == pytest.approx(0.6)
    assert facts["routing_slack_max"] == pytest.approx(0.05)
    assert facts["max_abs_logit_diff"] == pytest.approx(0.04, abs=2e-3)
    assert facts["rms_logit_diff"] == pytest.approx(
        float(np.sqrt((0.01 ** 2 + 0.04 ** 2 + 0.03 ** 2 + 0.02 ** 2) / 20)), rel=0.05)
    tolerance = {"prefill_logit": 0.0625, "rms_logit": 0.02, "token_gap": 0.0625,
                 "routing_agree_share": 0.5, "routing_slack": 0.1, "unfollowed_logit": 0.25}
    facts.update(prefill_max_abs_logit_diff=0.1, token_gap_max=0.05)
    assert within(facts, tolerance)
    for key, value in (("routing_agree_share", 0.7), ("routing_slack", 0.04),
                       ("prefill_logit", 0.03), ("rms_logit", 0.005),
                       ("token_gap", 0.04), ("unfollowed_logit", 0.08)):
        assert not within(facts, dict(tolerance, **{key: value})), key
    assert not within(dict(facts, finite=False), tolerance)
    assert not within({"error": "stream ended early"}, tolerance)
    # a dense architecture's facts have no routing and two bounds
    dense = {"finite": True, "prefill_max_abs_logit_diff": 0.1, "token_gap_max": 0.0}
    assert within(dense, {"prefill_logit": 0.125, "token_gap": 0.0625})
    assert not within(dense, {"prefill_logit": 0.0625, "token_gap": 0.0625})


def _hand_built():
    config = manifest.load_json(
        os.path.join(HERE, "data", "configs", "tiny-moe.json"))
    layers, experts = 2, 8
    before = {"decode_steps": 10, "touched": [30, 40],
              "assignments": [[10] * experts, [10] * experts]}
    after = {"decode_steps": 110, "touched": [530, 640],
             "assignments": [[110] * experts, [10 + 50 * (i % 2 + 1) for i in range(experts)]]}
    return {
        "config": config, "device": {"kind": "TPU v5 lite"},
        "program_counters": {"before": {"moe": before}, "after": {"moe": after}},
        "scopes": {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                   "scope_s": {"moe.route": 0.02, "moe.experts": 0.18},
                   "kernel_s": {"moe_experts": 0.15}},
        "trace": {"busy_s": 0.6, "modules": {
            "jit__decode_impl": {"count": 50, "total_s": 0.5, "median_s": 0.01}}},
        "traced": {"start": 1.0, "stop": 3.0}, "window_s": 4.0, "pool": [],
        "records": [{"stamps": [0.5, 1.5, 2.5], "done": None, "prompt_len": 7,
                     "due": 0.1, "sent": 0.1, "asked": 9, "error": None}],
    }, layers, experts


def test_each_new_reader_on_a_hand_built_result(tiny_moe_benchmark):
    result, layers, experts = _hand_built()
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    touched = (500 + 600) / (100 * layers)
    assert line["moe_experts_touched_mean"] == {"value": touched, "unit": "experts"}
    assert line["moe_experts_busy_share"]["value"] == pytest.approx(40.0)
    # layer 0 is even (1.0); layer 1's busiest has 100 of a mean of 75
    assert line["moe_expert_load_max_over_mean"]["value"] == pytest.approx(
        (1.0 + 100 / 75) / 2)
    config = result["config"]
    expert = 3 * 64 * 32
    experts_bytes = layers * (touched * expert + 64 * experts) * 2
    assert flops_moe.experts_step_min_bytes(config, touched) == experts_bytes
    assert line["moe_experts_roofline"]["value"] == pytest.approx(
        100 * (experts_bytes / 819e9) / (0.2 / 50))
    live = 7 + 2  # prompt and the two tokens stamped by the middle (2.0)
    other = (layers * 4 * 64 * 64 + 64 * 256) * 2 + live * 2 * 4 * 16 * layers * 2
    assert flops_moe.decode_step_min_bytes(config, touched, live) == experts_bytes + other
    assert line["moe_decode_roofline"]["value"] == pytest.approx(
        100 * ((experts_bytes + other) / 819e9) / 0.01)
    assert flops_moe.experts_flops(config, 64) == 2 * 64 * expert


def test_new_readers_return_nothing_for_a_program_without_the_names(tiny_moe_benchmark):
    """The parent: no counters in ``runtime_info()``, no scope in the trace."""
    result, _, _ = _hand_built()
    result["program_counters"] = {"before": {"moe": None}, "after": {"moe": None}}
    result["scopes"]["scope_s"] = {"moe.route": 0.0, "moe.experts": 0.0}
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert not any(name.startswith("moe_") for name in line)
    result["scopes"] = None
    assert not any(name.startswith("moe_")
                   for name in cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))


HLO = """
HloModule jit__decode_impl

%fused_computation.3 (p: bf16[8,64]) -> f32[8,64] {
  %p = bf16[8,64]{1,0} parameter(0)
  ROOT %convert.9 = f32[8,64]{1,0} convert(%p), metadata={op_name="jit(_decode_impl)/M/layer_0/moe/moe.route/convert" stack_frame_id=3}
}

ENTRY %main.1 (a: bf16[8,64]) -> f32[8,64] {
  %a = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params"}
  %fusion.12 = f32[8,64]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_decode_impl)/M/layer_0/moe/moe.route/convert" stack_frame_id=3}
  %moe_experts.8 = f32[64,2048]{1,0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_impl)/M/layer_0/moe/moe.experts/jit(moe_experts)/pallas_call" stack_frame_id=9}
  ROOT %copy.4 = f32[8,64]{1,0} copy(%moe_experts.8), metadata={op_name="jit(_decode_impl)/M/layer_0/attn/not_moe.experts_really/copy"}
}
"""


def test_scopes_come_from_the_compiled_text_by_instruction_name():
    table = xplane_scopes.op_scopes(HLO, ("moe.route", "moe.experts"))
    assert table == {"convert.9": "moe.route", "fusion.12": "moe.route",
                     "moe_experts.8": "moe.experts"}
    # an event's name is its HLO line; its instruction name is the key
    assert xplane_scopes.instruction_name(
        "%moe_experts.8 = f32[64,2048]{1,0:T(8,128)S(1)} custom-call(s32[] %g)"
    ) == "moe_experts.8"


def test_scope_reader_on_a_trace_of_the_dense_model():
    """``data/decode_steps.xplane.pb`` is a trace of Mistral's decode steps
    (cut by ``cut_xplane.py``): the program ran, under none of the names."""
    path = os.path.join(HERE, "data", "decode_steps.xplane.pb")
    assert xplane_scopes.by_name(path, "_no_such_program", {}) is None
    table = {"fusion.12": "moe.experts"}
    found = xplane_scopes.by_name(path, "_decode_impl", table, ("moe_experts",))
    assert found is None or (
        found["executions"] > 0 and found["kernel_s"] == {"moe_experts": 0.0})
