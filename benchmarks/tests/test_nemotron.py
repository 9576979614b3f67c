"""The state-space cell (``nemotronsuper_chat_closed32``) at sizes a CPU can
hold: a sound run is correct with every tick on the state-space step and the
grouped-query kernel and no pair dropped, the control is not; the
configuration file against the catalog row's values and the cut's
arithmetic; the driver's mapping; the reference's recurrence, group norm and
routing against cases written out by hand; ``costs_nemotron.py`` and the
readers against counts made by hand."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs_nemotron, run

from . import tiny

CELL = "nemotronsuper_chat_closed32"
REFERENCE = run.load_by_path("references", "nemotron_h")
DRIVER = run.load_by_path("drivers", "generate_nemotron")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
F32 = jnp.float32
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
#: the catalog row's ``config`` (model-configs guide,
#: ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``), as published
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=4096, hybrid_override_pattern=PATTERN,
    intermediate_size=2688, layer_norm_epsilon=1e-05, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=128, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=2688, moe_latent_size=1024,
    moe_shared_expert_intermediate_size=5376, moe_shared_expert_overlap=False,
    mtp_hybrid_override_pattern="*E", n_group=1, n_groups=8,
    n_routed_experts=512, n_shared_experts=1, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=22,
    num_hidden_layers=88, num_key_value_heads=2, num_logits_to_keep=1,
    num_nextn_predict_layers=1, partial_rotary_factor=1,
    rescale_prenorm_residual=True, residual_in_fp32=False, rope_theta=10000,
    routed_scaling_factor=5, sliding_window=None, ssm_state_size=128,
    tie_word_embeddings=False, time_step_floor=0.0001, time_step_max=0.1,
    time_step_min=0.001, topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072)

tiny.SHRINK["generate_nemotron"] = dict(
    config=dict(hidden_size=64, num_attention_heads=16, num_key_value_heads=1,
                head_dim=8, mamba_num_heads=8, mamba_head_dim=8,
                ssm_state_size=16, n_groups=2, chunk_size=8,
                moe_latent_size=32, moe_intermediate_size=48,
                moe_shared_expert_intermediate_size=64, n_routed_experts=8,
                experts_held=[0, 8], published=dict(n_routed_experts=32),
                num_experts_per_tok=6, vocab_size=256,
                hybrid_override_pattern="*EMEM",
                layers_held=[36, 37, 38, 39, 40], num_hidden_layers=5,
                compute_dtype="float32", param_dtype="float32"),
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
def config():
    return run.load_json(run.HERE, "configs", "nemotron3_super_ep4_l11.json")


@pytest.fixture(scope="module")
def cell():
    return run.load_json(run.HERE, "workloads", f"{CELL}.json")


@pytest.fixture(scope="module")
def sound():
    return tiny.run_cell(CELL, seed=2147483999, seconds=2.0)


def test_sound_run_is_correct_on_the_step_and_the_kernel(sound):
    line, before = sound
    compared = {c["name"]: c for ln in before if "compared" in ln
                for c in ln["compared"]}
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in ("routed_pairs_dropped", "routed_pairs_misplaced",
                 "routed_pairs_missing",
                 "pairs_computed_for_an_expert_not_held",
                 "ticks_of_an_ssm_layer_off_ssm_decode_step",
                 "ssm_ticks_missing", "ssm_state_rows_missing",
                 "ticks_of_the_gqa_layer_off_the_grouped_query_kernel",
                 "gqa_ticks_missing", "failed_requests",
                 "streamed_unequal_to_final", "page_allocations_failed",
                 "compiles_in_window", "served_token_gap_mean",
                 "served_token_gap_max"):
        assert name in compared and compared[name]["limit"] is not None
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
    moved = [ln["samples"]["counters_moved"] for ln in before
             if "samples" in ln][0]
    assert moved["prefill_tokens"] > 0 < moved["attn_ticks_ssm"]
    # two ssm layers at this size: two states a live row a tick
    assert moved["ssm_state_rows"] % 2 == 0 and moved["ssm_state_rows"] > 0
    assert 0 < moved["moe_pairs_held"] < moved["moe_pairs_routed"]


def test_control_reads_three_times_the_sound_run():
    with tiny.shrunk():
        manifest = run.load_json(run.ROOT, "BENCHMARK.json")
        _, cell, config = run.find_cell(manifest, CELL)
        driver = DRIVER.Driver(cell, config, 4, REFERENCE)
        try:
            driver.warm()
            driver.window(2.0)
            sound = {c["name"]: c["value"] for c in driver.check()}
            control = driver.control()
        finally:
            driver.close()
    assert control["served_token_gap_mean"] > 0.05
    assert control["served_token_gap_mean"] \
        >= 3 * sound["served_token_gap_mean"], (sound, control)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_the_published_value(config, key):
    """Every key of the row at its published value, but the four the cut
    changes, which ``reduced`` lists and ``published`` keeps."""
    if key in config["reduced"]:
        assert config["published"][key] == PUBLISHED[key]
        assert config[key] != PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key]


def test_the_cut_is_one_whole_period(config):
    assert config["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) \
        == (40, 40, 8) and len(PATTERN) == 88 and "-" not in PATTERN
    held = config["layers_held"]
    assert held == list(range(36, 47))
    assert config["hybrid_override_pattern"] == PATTERN[36:47] \
        == "*EMEMEMEMEM"
    assert config["num_hidden_layers"] == len(held) == 11
    assert config["experts_held"] == [0, 128]
    assert config["n_routed_experts"] == 128
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    # the guide's floors: a whole period, 8 experts, an eighth of the ids
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["deployment"]["pipeline_stages"] == 8
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    for key in ("published", "deployment", "assumed", "departures"):
        assert config[key]
    assert "multi_token_prediction" in config["departures"]


def test_the_cuts_arithmetic(config):
    """ISSUE 45's count: 4,648M parameters, 9.30 GB in bf16."""
    D, L, F, Fs, V = 4096, 1024, 2688, 5376, 32768
    inner, conv = 128 * 64, 128 * 64 + 2 * 8 * 128
    held = config["deployment"]["parameters_held"]
    assert held["expert"] == 2 * L * F == 5_505_024
    assert held["mamba_layer"] == (D * (2 * inner + 2 * 8 * 128 + 128)
                                   + inner * D + conv * 5 + 3 * 128 + inner
                                   + D) == 109_640_064
    assert held["attention_layer"] == 2 * D * D + 2 * D * 256 + D
    assert held["routed_layer_outside_experts"] \
        == D * 512 + 512 + 2 * D * L + 2 * D * Fs + D
    assert held["routed_layer_128_experts"] \
        == held["routed_layer_outside_experts"] + 128 * held["expert"]
    total = (5 * held["mamba_layer"] + held["attention_layer"]
             + 5 * held["routed_layer_128_experts"] + 2 * V * D + D)
    assert held["total"] == total and held["bytes_bf16"] == 2 * total
    assert 4.64e9 < total < 4.66e9
    # a routed layer whole: 5.75 GB, so a chip cannot hold three
    assert 2 * (held["routed_layer_outside_experts"]
                + 512 * held["expert"]) > 5.7e9
    # above a quarter of the chip before a state or a page is allocated
    assert 2 * total > 0.25 * 16.9e9
    assert 1.2e11 < config["published"]["parameters"] < 1.21e11


def test_program_config_maps_the_file(config, cell):
    cfg = DRIVER.program_config(config, cell["max_len"])
    assert cfg.mixers == ("gqa",) + ("ssm",) * 5
    assert cfg.ffn == ("moe",) * 5 + ("none",)
    assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.vocab,
            cfg.max_len) == (4096, 32, 2, 128, 32768, 4096)
    assert cfg.ssm == (128, 64, 128, 8, 4, 128)
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.scale, r.d_expert,
            r.d_shared, r.latent, r.form) == (512, 0, 128, 22, 5.0, 2688,
                                              5376, 1024, "relu2")
    assert cfg.norm_eps == 1e-5 and cfg.qk_positions is False
    with pytest.raises(ValueError, match="disagree"):
        DRIVER.program_config(dict(config, experts_held=[0, 64]), 4096)
    with pytest.raises(ValueError, match="disagree"):
        DRIVER.program_config(dict(config, num_hidden_layers=10), 4096)


def test_the_mix_is_the_issues(cell):
    from benchmarks import traffic
    mix = traffic.load(cell["traffic"])
    assert (mix["clients"], mix["requests_per_client"]) == (32, 16)
    assert mix["prompt"] == dict(median=256, sigma=0.8, min=64, max=2048)
    assert mix["output"] == dict(median=128, sigma=0.5, min=32, max=512)
    assert (mix["max_total"], cell["max_len"], cell["slots"]) == (2560, 4096,
                                                                  32)
    assert (mix["ramp_seconds"], mix["start_stagger_s"],
            mix["check_requests"]) == (10, 0.1, 2)
    plans = traffic.closed_loop_requests(mix, 2147483999, 32768)
    sent = [(len(p), o) for plan in plans for p, o in plan]
    assert len(sent) == 512
    assert all(64 <= n <= 2048 and 32 <= o <= 512 and n + o <= 2560
               for n, o in sent)
    assert all(0 < t < 32768 for plan in plans for p, _ in plan for t in p)
    # the warm list covers every width a prompt's last window can pad to (a
    # carrying decoder pads no window under 64 lanes)
    chunk = cell["engine"]["prefill_chunk"]
    assert chunk == 256

    def last_window(n):
        return max(64, 1 << ((n % chunk or chunk) - 1).bit_length())
    assert {last_window(n) for n, _ in sent} \
        <= {last_window(n) for n in mix["warm"]["chunked_prompts"]}
    # short chat: a request's answer is a third of its tokens or more
    assert 3 * sum(o for _, o in sent) > sum(n for n, _ in sent)


def test_the_recurrence_by_hand():
    """One head of two channels on a state two wide, three tokens, every
    step written out in numpy; the gate before the norm, one group."""
    rng = np.random.default_rng(0)
    sizes = dict(mamba_num_heads=1, mamba_head_dim=2, ssm_state_size=2,
                 n_groups=1, conv_kernel=2, layer_norm_epsilon=1e-5)
    D, inner, conv = 3, 2, 2 + 2 * 2
    lp = {"in": {"w": rng.normal(0, 1, (D, 2 * inner + 4 + 1))},
          "conv": {"w": rng.normal(0, 1, (2, conv)),
                   "b": rng.normal(0, 1, conv)},
          "dt_bias": rng.normal(0, 1, 1), "a_log": rng.normal(0, 1, 1),
          "d": rng.normal(0, 1, 1), "o_norm": {"scale": rng.normal(0, 1, 2)},
          "o": {"w": rng.normal(0, 1, (inner, D))}}
    x = rng.normal(0, 1, (3, D))
    zxd = x @ lp["in"]["w"]
    z, pre, dt = zxd[:, :2], zxd[:, 2:8], zxd[:, 8:]
    silu = lambda t: t / (1 + np.exp(-t))                     # noqa: E731
    state, want = np.zeros((2, 2)), []
    for t in range(3):
        prev = pre[t - 1] if t else np.zeros(conv)
        mixed = silu(prev * lp["conv"]["w"][0] + pre[t] * lp["conv"]["w"][1]
                     + lp["conv"]["b"])
        u, b, c = mixed[:2], mixed[2:4], mixed[4:]
        d = np.log1p(np.exp(dt[t, 0] + lp["dt_bias"][0]))
        state = (np.exp(-d * np.exp(lp["a_log"][0])) * state
                 + d * np.outer(u, b))
        y = (state @ c + lp["d"][0] * u) * silu(z[t])
        y = y / np.sqrt(np.mean(y * y) + 1e-5) * lp["o_norm"]["scale"]
        want.append(y @ lp["o"]["w"])
    f32 = lambda tree: jax.tree.map(                          # noqa: E731
        lambda t: jnp.asarray(t, F32), tree)
    got = REFERENCE.mamba(f32(x), f32(lp), sizes, lambda t: t)
    assert np.allclose(np.asarray(got), np.stack(want), atol=1e-5)


def test_routing_case_by_hand():
    """8 experts, top-3, x5: the bias changes the choice and not the
    weights, which add up to the scaling factor."""
    sizes = dict(num_experts_per_tok=3, routed_scaling_factor=5)
    logit = np.array([[2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0]], np.float32)
    bias = np.array([0, 0, 0, 0, 0, 0.5, 0, 0], np.float32)
    chosen, w = REFERENCE.route(jnp.asarray(logit), jnp.eye(8), bias, sizes,
                                lambda t: t)
    s = 1 / (1 + np.exp(-logit[0]))
    # by s + b: expert 5 (0.80 + 0.5), expert 0 (0.88), expert 4 (0.82)
    order = np.asarray(chosen)[0]
    assert sorted(order.tolist()) == [0, 4, 5]
    assert np.allclose(np.asarray(w)[0],
                       5 * s[order] / (s[0] + s[4] + s[5]), atol=1e-6)
    assert np.asarray(w).sum() == pytest.approx(5.0, abs=1e-5)


def test_costs_by_hand():
    # a live row of the cell: 128 heads of 64 x 128 float32, in and out
    assert costs_nemotron.ssm_state_bytes(1, 128, 64, 128) == 8_388_608
    # 32 rows x 5 layers a tick: 1.34 GB
    assert costs_nemotron.ssm_state_bytes(160, 128, 64, 128) \
        == 160 * 8_388_608
    # an expert in the latent: 11.0 MB where costs_moe's gated count on the
    # model's width would say 66 MB
    assert costs_nemotron.latent_expert_bytes(1024, 2688) == 11_010_048
    assert costs_nemotron.latent_experts_touched_bytes(96, 1024, 2688) \
        == 96 * 11_010_048


def test_the_readers_read_this_configuration(config, cell):
    """The accepted routed and grouped-query readers and the three new ones
    on this configuration: FIVE routed layers, 128 experts held, one
    ``full_attention`` layer of 2 KV heads of 128."""
    from benchmarks.layer_metrics import _routed
    assert _routed.routed_layers(config) == 5
    assert config["layer_types"].count("full_attention") == 1
    assert config["layer_types"].count("mamba") == 5
    # 100 ticks of 32 rows in the traced 4 s of a 40 s window of 1,000
    events = [(0.04 * i, 500) for i in range(1000) for _ in range(32)]
    touched = 1000 * 5 * 96
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={},
                 modules={"jit_tick": [1.6, 100]},
                 module_ops={"jit_tick/_moe_experts_call": [0.8, 500],
                             "jit_tick/_ssm_step_call": [0.2, 500],
                             "jit_tick/_pa_gqa_call": [0.01, 100]})
    counters = dict(
        kv_stats={"attn_ticks_kernel": 1400, "prefill_chunks": 400,
                  "prefill_tokens": 80_000, "moe_experts_touched": touched,
                  "ssm_state_rows": 1000 * 5 * 32},
        token_events=events, traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=40.0,
        window_elapsed_s=40.0)

    def read(name):
        return run.load_by_path("layer_metrics", name).read(
            trace, counters, cell, config, PEAK)
    assert read("moe_experts_touched_pct.generate") == pytest.approx(
        100 * 96 / 128)
    assert read("moe_device_share_pct.generate") == pytest.approx(50.0)
    assert read("ssm_device_share_pct.generate") == pytest.approx(12.5)
    assert read("latent_moe_expert_roofline") == pytest.approx(
        100 * (touched / 10 * 11_010_048 / 819e9) / 0.8)
    assert read("ssm_state_roofline") == pytest.approx(
        100 * (100 * 5 * 32 * 8_388_608 / 819e9) / 0.2)
    # K and V of 500 positions of 2 KV heads of 128, one layer: 1 KB a token
    assert read("gqa_attn_roofline") == pytest.approx(
        100 * (3200 * 500 * 1024 / 819e9) / 0.01)
    assert read("prefill_tokens_per_s.generate") == pytest.approx(2000.0)
    for name in ("latent_moe_expert_roofline", "ssm_state_roofline"):
        assert 0 < read(name) < 100


def test_new_readers_return_none_where_the_program_counts_nothing(config,
                                                                   cell):
    """On a program that lacks the counter and the kernel (the parent
    commit), the new readers return None and do not raise; on another
    configuration's file (no latent) the latent reader returns None."""
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={}, modules={},
                 module_ops={"jit_tick/fusion": [1.0, 10]})
    counters = dict(kv_stats={"attn_ticks_kernel": 10}, token_events=[],
                    traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=51.0,
                    window_elapsed_s=51.0)
    for name in ("latent_moe_expert_roofline", "ssm_state_roofline",
                 "ssm_device_share_pct.generate"):
        reader = run.load_by_path("layer_metrics", name)
        assert reader.read(trace, counters, cell, config, PEAK) is None
        assert reader.read(trace, {}, {}, {}, PEAK) is None


def test_the_cell_names_what_the_trace_shows(cell):
    assert set(cell["trace_ops"]) == {"ssm_decode", "moe_experts",
                                      "moe_routing", "gqa_decode", "tick"}
    assert cell["trace_ops"]["ssm_decode"] == "^jit_tick/_ssm_step_call$"
    assert cell["driver"] == "generate_nemotron"
    assert os.path.exists(os.path.join(run.HERE, "traffic",
                                       cell["traffic"] + ".json"))
    assert json.dumps(cell["limits"])
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    lists = [m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())]
    assert {"latent_moe_expert_roofline", "ssm_state_roofline",
            "ssm_device_share_pct.generate", "gqa_attn_roofline",
            "moe_device_share_pct.generate",
            "moe_experts_touched_pct.generate",
            "prefill_tokens_per_s.generate"} <= set(lists)
    # its accepted reader counts a gated expert on the model's width
    assert "moe_expert_roofline" not in lists
