"""The conv + grouped-query cell (``lfm2_ragchat_closed32``) at sizes a CPU
can hold: a sound run is correct with every tick on the grouped-query kernel
and no pair dropped, the control is not; the configuration file against the
catalog row's values and the cut's arithmetic; the driver's mapping; the
reference's convolution and routing against cases written out by hand;
``costs_lfm2.py`` and the readers against counts made by hand."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs_lfm2, costs_moe, run

from . import tiny

CELL = "lfm2_ragchat_closed32"
REFERENCE = run.load_by_path("references", "lfm2_moe")
DRIVER = run.load_by_path("drivers", "generate_lfm2")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: the catalog row's ``config`` (model-configs guide, ``LFM2-24B-A2B``): the
#: numbers at the top level and the nested group, as published
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048,
    intermediate_size=11776, max_position_embeddings=128000,
    model_type="lfm2_moe", moe_intermediate_size=1536, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
    num_experts=64, num_experts_per_tok=4, num_hidden_layers=40,
    num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)

tiny.SHRINK["generate_lfm2"] = dict(
    config=dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, moe_intermediate_size=32,
                num_experts=8, num_experts_per_tok=2, experts_held=[0, 8],
                vocab_size=256,
                layer_types=["conv", "conv", "full_attention", "conv"],
                layers_held=[0, 8, 10, 11], num_hidden_layers=4,
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
    return run.load_json(run.HERE, "configs", "lfm2_24b_a2b_pp5_l9.json")


@pytest.fixture(scope="module")
def cell():
    return run.load_json(run.HERE, "workloads", f"{CELL}.json")


@pytest.fixture(scope="module")
def sound():
    return tiny.run_cell(CELL, seed=2147483999, seconds=2.0)


def test_sound_run_is_correct_on_the_grouped_query_kernel(sound):
    line, before = sound
    compared = {c["name"]: c for ln in before if "compared" in ln
                for c in ln["compared"]}
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in ("routed_pairs_dropped", "routed_pairs_misplaced",
                 "routed_pairs_not_on_a_held_expert",
                 "ticks_of_a_gqa_layer_off_the_grouped_query_kernel",
                 "gqa_ticks_missing", "conv_ticks_missing",
                 "failed_requests", "streamed_unequal_to_final",
                 "page_allocations_failed", "compiles_in_window",
                 "served_token_gap_mean", "served_token_gap_max"):
        assert name in compared and compared[name]["limit"] is not None
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
    moved = [ln["samples"]["counters_moved"] for ln in before
             if "samples" in ln][0]
    assert moved["prefill_tokens"] > 0 < moved["attn_ticks_conv"]


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
    assert control["served_token_gap_mean"] \
        >= 3 * sound["served_token_gap_mean"], (sound, control)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_the_published_value(config, key):
    """Every key of the row at its published value, but the three the cut
    changes, which ``reduced`` lists and ``published`` keeps."""
    if key in config["reduced"]:
        assert config["published"][key] == PUBLISHED[key]
        assert config[key] != PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key]


def test_the_cut_is_two_periods_after_one_dense_layer(config):
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers"]
    held = config["layers_held"]
    assert held == [0] + list(range(8, 16))
    types = config["published"]["layer_types"]
    assert len(types) == 40 and types.count("full_attention") == 10
    assert [i for i, t in enumerate(types) if t == "full_attention"] \
        == list(range(2, 40, 4))
    assert config["layer_types"] == [types[i] for i in held]
    assert config["num_hidden_layers"] == len(held) == 9
    assert config["experts_held"] == [0, 64]
    assert config["num_dense_layers"] == config["first_k_dense_replace"] == 1
    assert config["deployment"]["pipeline_stages"] == 5
    assert config["deployment"]["chips_sharing_a_layer"] == 1


def test_the_cuts_arithmetic(config):
    """ISSUE 38's count: 5.18-5.31B parameters, 10.4-10.6 GB in bf16."""
    D, F, ff, V = 2048, 1536, 11776, 65536
    expert = 3 * D * F
    assert expert == 9_437_184 and 2 * expert == 18_874_368
    held = config["deployment"]["parameters_held"]
    conv = 4 * D * D + 3 * D
    attn = 2 * D * D + 2 * D * 512 + 2 * 64
    routed = 64 * expert + D * 64 + 64
    assert held["layer_0"] == conv + 3 * D * ff + 2 * D
    assert held["conv_routed_layer"] == conv + routed + 2 * D
    assert held["attention_routed_layer"] == attn + routed + 2 * D
    total = (held["layer_0"] + 6 * held["conv_routed_layer"]
             + 2 * held["attention_routed_layer"] + 2 * V * D + D)
    assert held["total"] == total and held["bytes_bf16"] == 2 * total
    assert 5.18e9 < total < 5.32e9
    # above a quarter of the chip before a page is allocated
    assert 2 * total > 0.25 * 16.9e9


def test_program_config_maps_the_file(config, cell):
    cfg = DRIVER.program_config(config, cell["max_len"])
    assert cfg.mixers.count("conv") == 7 and cfg.mixers.count("gqa") == 2
    assert cfg.ffn == ("dense",) + ("moe",) * 8
    assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab, cfg.max_len) == (2048, 32, 8, 64, 11776, 65536, 5120)
    assert cfg.routed.held == 64 and cfg.routed.d_expert == 1536
    assert cfg.conv.taps == 3 and cfg.norm_eps == 1e-5
    assert cfg.rope_theta == 1e6
    with pytest.raises(ValueError, match="every expert is held"):
        DRIVER.program_config(dict(config, experts_held=[0, 32]), 5120)
    with pytest.raises(ValueError, match="disagree"):
        DRIVER.program_config(dict(config, num_hidden_layers=8), 5120)


def test_the_mix_is_the_issues(cell):
    from benchmarks import traffic
    mix = traffic.load(cell["traffic"])
    assert (mix["clients"], mix["requests_per_client"]) == (32, 8)
    assert mix["prompt"] == dict(median=1024, sigma=0.8, min=256, max=4096)
    assert mix["output"] == dict(median=256, sigma=0.5, min=64, max=768)
    assert (mix["max_total"], cell["max_len"], cell["slots"]) == (4864, 5120,
                                                                  32)
    assert (mix["ramp_seconds"], mix["start_stagger_s"],
            mix["check_requests"]) == (10, 0.1, 2)
    plans = traffic.closed_loop_requests(mix, 2147483999, 65536)
    sent = [(len(p), o) for plan in plans for p, o in plan]
    assert len(sent) == 256
    assert all(256 <= n <= 4096 and 64 <= o <= 768 and n + o <= 4864
               for n, o in sent)
    # the warm list covers every width a prompt's last chunk can pad to
    chunk = cell["engine"]["prefill_chunk"]
    assert 256 <= chunk <= 512

    def last_window(n):
        return max(8, 1 << ((n % chunk or chunk) - 1).bit_length())
    assert {last_window(n) for n, _ in sent} \
        <= {last_window(n) for n in mix["warm"]["chunked_prompts"]}
    # prompts are longer than answers: the mix's point
    assert sum(n for n, _ in sent) > 3 * sum(o for _, o in sent)


def test_three_tap_convolution_by_hand():
    """Four tokens of two channels, every step written out in numpy."""
    rng = np.random.default_rng(0)
    D = 2
    lp = {"in": {"w": rng.normal(0, 1, (D, 3 * D))},
          "o": {"w": rng.normal(0, 1, (D, D))},
          "taps": rng.normal(0, 1, (3, D))}
    x = rng.normal(0, 1, (4, D))
    bcu = x @ lp["in"]["w"]
    b, c, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
    z = b * u
    want = []
    for t in range(4):
        acc = sum(lp["taps"][j] * z[t - 2 + j] for j in range(3)
                  if t - 2 + j >= 0)
        want.append((c[t] * acc) @ lp["o"]["w"])
    import jax
    f32 = lambda tree: jax.tree.map(                          # noqa: E731
        lambda t: jnp.asarray(t, jnp.float32), tree)
    got = REFERENCE.short_conv(f32(x), f32(lp), dict(conv_L_cache=3),
                               lambda t: t)
    assert np.allclose(np.asarray(got), np.stack(want), atol=1e-5)


def test_routing_case_by_hand():
    """8 experts, top-2: the bias changes the choice and not the weights."""
    sizes = dict(num_experts_per_tok=2, routed_scaling_factor=1)
    logit = np.array([[2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0]], np.float32)
    bias = np.array([0, 0, 0, 0, 0, 0.5, 0, 0], np.float32)
    chosen, w = REFERENCE.route(jnp.asarray(logit), jnp.eye(8), bias, sizes,
                                lambda t: t)
    s = 1 / (1 + np.exp(-logit[0]))
    # by s + b: expert 5 (0.80 + 0.5) then expert 0 (0.88); expert 4 (0.82)
    # would be second without the bias
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 5]
    order = np.asarray(chosen)[0]
    assert np.allclose(np.asarray(w)[0], s[order] / (s[0] + s[5]), atol=1e-6)


def test_gqa_bytes_by_hand():
    # two layers, contexts 100 and 28: 128 positions of 8 heads x 64 x K, V
    assert costs_lfm2.gqa_decode_bytes([100, 28], 2, 8, 64) \
        == 2 * 128 * 8 * 64 * 2 * 2 == 4096 * 128
    # ISSUE 38: 4 KB a cached token over the two attention layers
    assert costs_lfm2.gqa_decode_bytes([1], 2, 8, 64) == 4096


def test_the_routed_readers_read_this_configuration(config, cell):
    """The three accepted routed readers on this configuration: 8 routed
    layers, 64 experts held, 18,874,368 bytes an expert."""
    from benchmarks.layer_metrics import _routed
    assert _routed.routed_layers(config) == 8
    assert config["experts_held"][1] - config["experts_held"][0] == 64
    assert costs_moe.expert_bytes(config["hidden_size"],
                                  config["moe_intermediate_size"]) \
        == 18_874_368
    # 100 ticks of 32 rows in the traced 4 s of a 40 s window of 1,000
    events = [(0.04 * i, 1000) for i in range(1000) for _ in range(32)]
    touched = 1000 * 8 * 56
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={},
                 modules={"jit_tick": [1.0, 100]},
                 module_ops={"jit_tick/_moe_experts_call": [0.9, 800],
                             "jit_tick/_pa_gqa_call": [0.02, 200]})
    counters = dict(
        kv_stats={"attn_ticks_kernel": 1100, "prefill_chunks": 100,
                  "prefill_tokens": 40_000, "moe_experts_touched": touched},
        token_events=events, traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=40.0,
        window_elapsed_s=40.0)

    def read(name):
        return run.load_by_path("layer_metrics", name).read(
            trace, counters, cell, config, PEAK)
    assert read("moe_experts_touched_pct.generate") == pytest.approx(
        100 * 56 / 64)
    assert read("moe_device_share_pct.generate") == pytest.approx(90.0)
    assert read("moe_expert_roofline") == pytest.approx(
        100 * (touched / 10 * 18_874_368 / 819e9) / 0.9)
    assert read("gqa_attn_roofline") == pytest.approx(
        100 * (3200 * 1000 * 4096 / 819e9) / 0.02)
    assert read("prefill_tokens_per_s.generate") == pytest.approx(1000.0)


def test_new_readers_return_none_where_the_program_counts_nothing(config,
                                                                   cell):
    """On a program that lacks the counter and the kernel (the parent
    commit), the new readers return None and do not raise."""
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={}, modules={},
                 module_ops={"jit_tick/fusion": [1.0, 10]})
    counters = dict(kv_stats={"attn_ticks_kernel": 10}, token_events=[],
                    traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=51.0,
                    window_elapsed_s=51.0)
    for name in ("gqa_attn_roofline", "prefill_tokens_per_s.generate"):
        reader = run.load_by_path("layer_metrics", name)
        assert reader.read(trace, counters, cell, config, PEAK) is None
        assert reader.read(trace, {}, {}, {}, PEAK) is None


def test_the_cell_names_what_the_trace_shows(cell):
    assert set(cell["trace_ops"]) == {"gqa_decode", "moe_experts",
                                      "moe_routing", "tick"}
    assert cell["driver"] == "generate_lfm2"
    assert os.path.exists(os.path.join(run.HERE, "traffic",
                                       cell["traffic"] + ".json"))
    assert json.dumps(cell["limits"])
