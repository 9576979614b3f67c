"""The routed cell (``lingflash_reason_closed32``) at sizes a CPU can hold: a
sound run is correct with every tick on the three kernels and no pair
dropped, the control is not; the reference against a three-token delta rule
and a routing case written out by hand; ``costs_moe.py`` against counts made
by hand."""

import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs_moe, run

from . import tiny

CELL = "lingflash_reason_closed32"
REFERENCE = run.load_by_path("references", "ling_flash")

tiny.SHRINK["generate_ling"] = dict(
    config=dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                head_dim=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, kv_lora_rank=32, moe_intermediate_size=32,
                moe_shared_expert_intermediate_size=32, n_group=4,
                topk_group=2, num_experts_per_tok=4, num_experts=8,
                experts_held=[0, 8], published={"num_experts": 32},
                vocab_size=256, layer_group_size=3, layers_held=[0, 1, 2],
                num_hidden_layers=3, compute_dtype="float32",
                param_dtype="float32"),
    cell=dict(slots=4, max_len=128, trace_seconds=1,
              engine={"page_size": 8, "prefill_chunk": 32}),
    mix=dict(clients=4, requests_per_client=4,
             prompt={"median": 28, "sigma": 0.6, "min": 9, "max": 70},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             max_total=128, ramp_seconds=1, check_requests=2,
             warm=dict(plain_prompts=[0], chunked_prompts=[40, 48, 64],
                       defrag=dict(prompts=[100, 100, 40],
                                   outputs=[2, 2, 8]))))


@pytest.fixture(scope="module")
def sound():
    return tiny.run_cell(CELL, seed=2147483999, seconds=2.0)


def test_sound_run_is_correct_on_the_three_kernels(sound):
    line, before = sound
    compared = {c["name"]: c for ln in before if "compared" in ln
                for c in ln["compared"]}
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in ("routed_pairs_dropped",
                 "ticks_of_a_kda_layer_off_kda_decode_step",
                 "ticks_of_the_mla_layer_off_the_absorbed_kernel",
                 "pairs_computed_for_an_expert_not_held",
                 "served_token_gap_mean"):
        assert name in compared
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0


def test_control_reads_three_times_the_sound_run():
    with tiny.shrunk():
        manifest = run.load_json(run.ROOT, "BENCHMARK.json")
        _, cell, config = run.find_cell(manifest, CELL)
        driver = run.load_by_path("drivers", cell["driver"]).Driver(
            cell, config, 4, REFERENCE)
        try:
            driver.warm()
            driver.window(2.0)
            sound = {c["name"]: c["value"] for c in driver.check()}
            control = driver.control()
        finally:
            driver.close()
    assert control["served_token_gap_mean"] \
        >= 3 * sound["served_token_gap_mean"], (sound, control)


def test_three_token_delta_rule_by_hand():
    """One head of two channels over three tokens, every step written out in
    numpy: the convolution's taps on the rows before, SiLU, the l2 norms,
    the gate, then ``S_t = (I - b_t k_t k_t^T) diag(a_t) S_(t-1) + b_t k_t
    v_t^T`` and ``o_t = S_t^T q_t``, the head's RMSNorm and its gate."""
    rng = np.random.default_rng(0)
    D, d, K = 3, 2, 4
    sizes = dict(num_attention_heads=1, head_dim=d, short_conv_kernel_size=K,
                 kda_lower_bound=-5, rms_norm_eps=1e-6)
    lp = {n: {"w": rng.normal(0, 1, (D, d))} for n in "qkvf"}
    lp.update(b={"w": rng.normal(0, 1, (D, 1))},
              z={"w": rng.normal(0, 1, (D, 1))},
              o={"w": rng.normal(0, 1, (d, D))},
              dt_bias=rng.normal(0, 1, d), a_log=rng.normal(0, 0.5, 1),
              conv={n: rng.normal(0, 0.5, (K, d)) for n in "qkv"},
              o_norm={"scale": rng.uniform(0.5, 1.5, d)})
    x = rng.normal(0, 1, (3, D))

    def sigmoid(t):
        return 1 / (1 + np.exp(-t))

    def mixed(name, t):             # taps 0..3 on rows t-3..t, then SiLU
        pre = x @ lp[name]["w"]
        acc = sum(lp["conv"][name][j] * pre[t - (K - 1) + j]
                  for j in range(K) if t - (K - 1) + j >= 0)
        return acc * sigmoid(acc)

    S = np.zeros((d, d))
    want = []
    for t in range(3):
        q, k, v = (mixed(n, t) for n in "qkv")
        q = q / np.sqrt(q @ q + 1e-6) / np.sqrt(d)
        k = k / np.sqrt(k @ k + 1e-6)
        a = np.exp(-5 * sigmoid(np.exp(lp["a_log"][0])
                                * (x[t] @ lp["f"]["w"] + lp["dt_bias"])))
        beta = sigmoid(x[t] @ lp["b"]["w"])[0]
        S = np.diag(a) @ S
        S = S - beta * np.outer(k, k @ S) + beta * np.outer(k, v)
        o = S.T @ q
        o = o / np.sqrt(np.mean(o * o) + 1e-6) * lp["o_norm"]["scale"]
        want.append((o * sigmoid(x[t] @ lp["z"]["w"])) @ lp["o"]["w"])
    f32 = lambda tree: __import__("jax").tree.map(            # noqa: E731
        lambda t: jnp.asarray(t, jnp.float32), tree)
    got = REFERENCE.kda(f32(x), f32(lp), sizes, lambda t: t)
    assert np.allclose(np.asarray(got), np.stack(want), atol=2e-5)


def test_routing_case_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, top-2: scores chosen so
    that the bias changes the choice and not the weights."""
    sizes = dict(n_group=4, topk_group=2, num_experts_per_tok=2,
                 routed_scaling_factor=2.5)
    logit = np.array([[2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0]], np.float32)
    bias = np.array([0, 0, 0, 0, 0, 0.5, 0, 0], np.float32)
    # x = the logits, router = identity
    chosen, w = REFERENCE.route(jnp.asarray(logit), jnp.eye(8), bias, sizes,
                                lambda t: t)
    s = 1 / (1 + np.exp(-logit[0]))
    # group scores (sum of both members' s + b): g0 = s0 + s1, g2 = s4 + s5
    # + 0.5 lead; inside them the best two by s + b are expert 5 (s5 + 0.5)
    # and expert 0
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 5]
    order = np.asarray(chosen)[0]
    want = 2.5 * s[order] / (s[0] + s[5])
    assert np.allclose(np.asarray(w)[0], want, atol=1e-6)


def test_a_token_whose_experts_lie_elsewhere_adds_only_the_shared_expert():
    """The share: experts [4, 8) held, the token chooses 0 and 5: expert 5's
    part and the shared expert's, not expert 0's."""
    rng = np.random.default_rng(0)
    D, F = 8, 4
    sizes = REFERENCE._static(dict(
        n_group=4, topk_group=2, num_experts_per_tok=2,
        routed_scaling_factor=2.5, experts_held=[4, 8]))
    x = np.zeros((1, D), np.float32)
    x[0] = [2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0]
    bias = np.array([0, 0, 0, 0, 0, 0.5, 0, 0], np.float32)
    p = {"router": {"w": jnp.eye(8)}, "bias": jnp.asarray(bias),
         "experts": {"gate_up": jnp.asarray(rng.normal(0, 1, (4, D, 2 * F)),
                                            jnp.float32),
                     "down": jnp.asarray(rng.normal(0, 1, (4, F, D)),
                                         jnp.float32)},
         "shared": {n: {"w": jnp.asarray(rng.normal(0, 1, shape),
                                         jnp.float32)}
                    for n, shape in (("gate", (D, F)), ("up", (D, F)),
                                     ("down", (F, D)))}}
    got = np.asarray(REFERENCE.routed_ffn(jnp.asarray(x), p, sizes))[0]

    def swiglu(g, u, d):
        h = x[0] @ g
        return ((h / (1 + np.exp(-h))) * (x[0] @ u)) @ d
    s = 1 / (1 + np.exp(-x[0]))
    gu = np.asarray(p["experts"]["gate_up"][1])             # expert 5
    want = 2.5 * s[5] / (s[0] + s[5]) * swiglu(
        gu[:, :F], gu[:, F:], np.asarray(p["experts"]["down"][1]))
    want = want + swiglu(*(np.asarray(p["shared"][n]["w"])
                           for n in ("gate", "up", "down")))
    assert np.allclose(got, want, atol=1e-4)


def test_expert_bytes_by_hand():
    # one published expert: gate, up, down of 2560 x 768 in bf16
    assert costs_moe.expert_bytes(2560, 768) == 3 * 2560 * 768 * 2 \
        == 11_796_480
    # 50 distinct experts a layer, 6 layers, one tick
    assert costs_moe.experts_touched_bytes(300, 2560, 768) \
        == 300 * 11_796_480


def test_expected_experts_touched():
    # 64 pairs over 128 experts reach about 50 of them (ISSUE 35's plan)
    assert costs_moe.expected_experts_touched(128, 64) \
        == pytest.approx(128 * (1 - (127 / 128) ** 64))
    assert 50 < costs_moe.expected_experts_touched(128, 64) < 51
    assert costs_moe.expected_experts_touched(8, 0) == 0


def test_latent_bytes_by_hand():
    # one layer, contexts 100 and 28: 128 rows of 576 bf16 values
    assert costs_moe.latent_decode_bytes([100, 28], 1, 512, 64) \
        == 128 * 576 * 2


def test_readers_return_none_where_the_program_counts_nothing():
    """On a program that lacks the counters and the kernels (the parent
    commit), every new reader returns None and does not raise."""
    config = json.load(open(run.os.path.join(
        run.HERE, "configs", "ling3_flash_ep4_l7.json")))
    cell = run.load_json(run.HERE, "workloads", f"{CELL}.json")
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={}, modules={},
                 module_ops={"jit_tick/fusion": [1.0, 10]})
    counters = dict(kv_stats={"attn_ticks_kernel": 10}, token_events=[],
                    traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=51.0)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for name in ("moe_expert_roofline", "moe_device_share_pct.generate",
                 "moe_experts_touched_pct.generate", "kda_state_roofline",
                 "latent_attn_roofline"):
        reader = run.load_by_path("layer_metrics", name)
        assert reader.read(trace, counters, cell, config, peak) is None
        assert reader.read(trace, {}, {}, {}, peak) is None


def test_readers_read_a_made_up_trace():
    config = json.load(open(run.os.path.join(
        run.HERE, "configs", "ling3_flash_ep4_l7.json")))
    cell = run.load_json(run.HERE, "workloads", f"{CELL}.json")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 100 ticks of 32 rows in the traced 4 s of a 40 s window of 1,000
    events = [(0.04 * i, 1000) for i in range(1000) for _ in range(32)]
    touched = 1000 * 6 * 50
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={},
                 modules={"jit_tick": [1.0, 100]},
                 module_ops={"jit_tick/_moe_experts_call": [0.5, 600],
                             "jit_tick/_kda_step_call": [0.1, 600],
                             "jit_tick/_pa_latent_call": [0.05, 100]})
    counters = dict(
        kv_stats={"attn_ticks_kernel": 1100, "prefill_chunks": 100,
                  "moe_experts_touched": touched},
        token_events=events, traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=40.0)

    def read(name):
        return run.load_by_path("layer_metrics", name).read(
            trace, counters, cell, config, peak)
    assert read("moe_experts_touched_pct.generate") == pytest.approx(
        100 * 50 / 128)
    assert read("moe_device_share_pct.generate") == pytest.approx(50.0)
    # a tenth of the window's touched experts lie in the traced stretch
    assert read("moe_expert_roofline") == pytest.approx(
        100 * (touched / 10 * 11_796_480 / 819e9) / 0.5)
    assert read("kda_state_roofline") == pytest.approx(
        100 * (3200 * 6 * 2 * 32 * 128 * 128 * 4 / 819e9) / 0.1)
    assert read("latent_attn_roofline") == pytest.approx(
        100 * (3200 * 1000 * 576 * 2 / 819e9) / 0.05)
    assert math.isfinite(read("latent_attn_roofline"))
