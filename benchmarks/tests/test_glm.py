"""The shared-context latent cell (``glmflash_repoctx_shared32``) at sizes a
CPU can hold: a sound run is correct with every tick on the absorbed kernel,
every admission a hit whose shared tokens are its whole context, and no pair
dropped; the control is not; the configuration file against the catalog
row's values and the cut's arithmetic; the driver's mapping and the dealing of
callers to contexts; the reference's latent attention and routing against
cases written out by hand; ``costs_glm.py`` and the readers against counts
made by hand."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs_glm, costs_moe, run, traffic

from . import tiny

CELL = "glmflash_repoctx_shared32"
REFERENCE = run.load_by_path("references", "glm4_moe_lite")
DRIVER = run.load_by_path("drivers", "generate_glm")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: the catalog row's ``config`` (model-configs guide, ``GLM-4.7-Flash``), as
#: published
PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2048,
    intermediate_size=10240, max_position_embeddings=202752,
    model_type="glm4_moe_lite", moe_intermediate_size=1536,
    topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
    n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
    routed_scaling_factor=1.8, num_experts_per_tok=4, first_k_dense_replace=1,
    num_hidden_layers=47, num_key_value_heads=20, num_nextn_predict_layers=1,
    partial_rotary_factor=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=1000000, tie_word_embeddings=False, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, vocab_size=154880)

tiny.SHRINK["generate_glm"] = dict(
    config=dict(hidden_size=64, intermediate_size=128, num_attention_heads=5,
                num_key_value_heads=5, q_lora_rank=24, kv_lora_rank=32,
                qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                moe_intermediate_size=32, n_routed_experts=8,
                num_experts_per_tok=2, experts_held=[0, 8], vocab_size=256,
                layers_held=[0, 7, 8], num_hidden_layers=3,
                compute_dtype="float32", param_dtype="float32"),
    cell=dict(slots=4, max_len=256, trace_seconds=1,
              engine={"page_size": 8, "prefill_chunk": 32, "kv_pages": 120}),
    mix=dict(clients=4, requests_per_client=4,
             prompt={"median": 12, "sigma": 0.6, "min": 4, "max": 30},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             max_total=64, ramp_seconds=1, check_requests=2,
             documents=dict(count=2, shortest=96, step=64),
             warm=dict(plain_prompts=[0], questions=[8, 16, 32],
                       register_output=2,
                       defrag=dict(prompts=[100, 100, 100, 16],
                                   outputs=[2, 2, 2, 8]))))


@pytest.fixture(scope="module")
def config():
    return run.load_json(run.HERE, "configs", "glm47_flash_l7.json")


@pytest.fixture(scope="module")
def cell():
    return run.load_json(run.HERE, "workloads", f"{CELL}.json")


@pytest.fixture(scope="module")
def sound():
    return tiny.run_cell(CELL, seed=2147483999, seconds=2.0)


def test_sound_run_is_correct_and_every_admission_shares_its_context(sound):
    line, before = sound
    compared = {c["name"]: c for ln in before if "compared" in ln
                for c in ln["compared"]}
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in ("routed_pairs_dropped", "routed_pairs_misplaced",
                 "routed_pairs_missing", "routed_pairs_not_on_a_held_expert",
                 "ticks_of_an_mla_layer_off_the_absorbed_kernel",
                 "latent_ticks_missing", "prefix_misses_in_window",
                 "shared_tokens_short_of_the_contexts", "prefix_hits_missing",
                 "failed_requests", "streamed_unequal_to_final",
                 "page_allocations_failed", "compiles_in_window",
                 "served_token_gap_mean", "served_token_gap_max"):
        assert name in compared and compared[name]["limit"] is not None
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
    moved = [ln["samples"]["counters_moved"] for ln in before
             if "samples" in ln][0]
    assert moved["prefix_hits"] > 0 == moved["prefix_misses"]
    assert moved["prefix_tokens_shared"] == moved["prefix_hit_tokens"] > 0
    assert moved["latent_window_keys"] >= moved["latent_window_context"] > 0
    assert moved["prefill_tokens"] > 0 < moved["attn_ticks_latent"]
    where = [ln["setup_where"] for ln in before if "setup_where" in ln][0]
    assert {"defrag_s", "register_s", "warm_hits_s"} <= set(where)


def test_control_reads_three_times_the_sound_run():
    with tiny.shrunk():
        manifest = run.load_json(run.ROOT, "BENCHMARK.json")
        _, cell, config = run.find_cell(manifest, CELL)
        driver = DRIVER.Driver(cell, config, 4, REFERENCE)
        try:
            driver.warm()
            result = driver.window(2.0)
            sound = {c["name"]: c["value"] for c in driver.check()}
            control = driver.control()
        finally:
            driver.close()
    assert control["served_token_gap_mean"] \
        >= 3 * sound["served_token_gap_mean"], (sound, control)
    # the counts of the traced stretch (1 s of the 2) are read apart
    traced = result["counters"]["kv_stats_traced"]
    whole = result["counters"]["kv_stats"]
    assert 0 < traced["attn_ticks_kernel"] < whole["attn_ticks_kernel"]


def test_the_counters_are_read_between_two_steps_of_the_engine():
    """A step counts an admission's shared pages (the pool's
    ``prefix_tokens_shared``) before the block table's upload and its hit's
    tokens (the decoder's ``prefix_hit_tokens``) after it; a window edge that
    read between the two called a sound run incorrect
    (``shared_tokens_short_of_the_contexts``; PR 42's first check, seed
    936306455). ``counters`` waits for the lock a step holds."""
    import threading
    with tiny.shrunk():
        manifest = run.load_json(run.ROOT, "BENCHMARK.json")
        _, cell, config = run.find_cell(manifest, CELL)
        driver = DRIVER.Driver(cell, config, 5, REFERENCE)
        try:
            read = []
            reader = threading.Thread(
                target=lambda: read.append(driver.counters()))
            with driver.engine.decoder._engine_lock:    # a step is running
                reader.start()
                reader.join(0.3)
                assert reader.is_alive() and not read
            reader.join(30)
            assert not reader.is_alive()
        finally:
            driver.close()
    assert read[0]["prefix_hit_tokens"] == read[0]["prefix_tokens_shared"] == 0
    assert set(DRIVER.POOL_COUNTS) <= set(read[0])


def test_callers_are_dealt_to_the_contexts_four_a_context(cell, config):
    """The real traffic file, no engine: 32 plans, caller ``j`` on context
    ``j % 8``, every request its context plus a question under the context's
    key and length; the same seed the same plans, every seed the same
    lengths."""
    mix = traffic.load(cell["traffic"])
    assert (mix["clients"], mix["requests_per_client"],
            mix["callers_per_document"]) == (32, 8, 4)
    assert mix["documents"] == dict(count=8, shortest=16384, step=2048)
    assert mix["prompt"] == dict(median=128, sigma=0.7, min=32, max=512)
    assert mix["output"] == dict(median=192, sigma=0.5, min=64, max=512)
    assert (mix["ramp_seconds"], mix["start_stagger_s"],
            mix["check_requests"]) == (10, 0.1, 2)
    small = dict(mix, documents=dict(count=8, shortest=64, step=8))
    docs = DRIVER.docs.documents(small, 7, 1000)
    again = DRIVER.docs.documents(small, 7, 1000)
    assert all(a[0] == b[0] and np.array_equal(a[1], b[1])
               for a, b in zip(docs, again))
    assert sorted(len(d) for _, d in DRIVER.docs.documents(small, 8, 1000)) \
        == sorted(len(d) for _, d in docs) == [64 + 8 * i for i in range(8)]
    lengths = sorted(16384 + 2048 * i for i in range(8))
    assert lengths[-1] == 30720 and all(n % 256 == 0 for n in lengths)
    assert sum(lengths) == 188_416
    assert lengths[-1] + mix["max_total"] == mix["max_total_with_context"] \
        == 31744 <= cell["max_len"] - 1024
    plans = traffic.closed_loop_requests(mix, 2147483999, 154880)
    sent = [(len(p), o) for plan in plans for p, o in plan]
    assert len(plans) == 32 and len(sent) == 256
    assert all(32 <= n <= 512 and 64 <= o <= 512 and n + o <= 1024
               for n, o in sent)
    # every question is one window, and the warm list covers every width a
    # question can pad to (a carrying decoder pads no window under 64 lanes)
    chunk = cell["engine"]["prefill_chunk"]
    assert chunk == 512 >= max(n for n, _ in sent)

    def width(n):
        return max(64, 1 << (n - 1).bit_length())
    assert {width(n) for n, _ in sent} \
        <= {width(n) for n in mix["warm"]["questions"]}
    # the pool holds the working set: 736 stored pages, 5 a live row, trash
    pages = cell["engine"]["kv_pages"]
    assert sum(n // 256 for n in lengths) == 736
    assert 736 + 32 * 5 + 1 <= pages == 1024
    # and the warm-up's defragmentation is provoked: three long requests
    # retire under a short one, past the pool's threshold of a quarter
    d = mix["warm"]["defrag"]
    below = sum(-(-(n + o) // 256) for n, o in zip(d["prompts"][:-1],
                                                 d["outputs"][:-1]))
    assert below >= pages // 4 + 16


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_the_published_value(config, key):
    """Every key of the row at its published value, but the depth, which
    ``reduced`` lists and ``published`` keeps."""
    if key in config["reduced"]:
        assert config["published"][key] == PUBLISHED[key]
        assert config[key] != PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key]


def test_the_cut_is_six_routed_layers_after_the_dense_one(config):
    assert config["reduced"] == ["num_hidden_layers"]
    held = config["layers_held"]
    assert held == [0] + list(range(7, 13))
    assert config["num_hidden_layers"] == len(held) == 7
    assert config["experts_held"] == [0, 64]
    assert config["layer_group_size"] == 1
    assert config["deployment"]["pipeline_stages"] == 8
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    for section in ("published", "deployment", "assumed", "departures"):
        assert config[section]
    assert "multi_token_prediction" in config["departures"]
    assert "layer_group_size" in config["assumed"]


def test_the_cuts_arithmetic(config):
    """ISSUE 42's count: a latent mixer 21.76M, a routed layer 635.3M =
    1.271 GB, layer 0 84.7M, the ends 634.4M: 9.06 GB in bf16."""
    D, F, ff, V, H = 2048, 1536, 10240, 154880, 20
    expert = 3 * D * F
    assert expert == 9_437_184
    mla = (D * 768 + 768 * H * 256 + D * 576 + 512 * H * 448 + H * 256 * D
           + 768 + 512)
    assert mla == 21_759_232
    held = config["deployment"]["parameters_held"]
    routed = 65 * expert + D * 64 + 64
    assert held["layer_0"] == mla + 3 * D * ff + 2 * D
    assert held["routed_layer"] == mla + routed + 2 * D == 635_311_424
    assert held["embedding_and_head"] == 2 * V * D
    total = held["layer_0"] + 6 * held["routed_layer"] + 2 * V * D + D
    assert held["total"] == total and held["bytes_bf16"] == 2 * total
    assert 9.05e9 < 2 * total < 9.07e9
    # above a quarter of the chip before a page is allocated, and seven
    # routed layers would be the 10.33 GB the issue names
    assert 2 * total > 0.25 * 16.9e9
    assert 10.32e9 < 2 * (total + held["routed_layer"]) < 10.34e9
    # the pool: 1,280 B a token a layer over 262,144 tokens
    assert 7 * 640 * 2 * 1024 * 256 == 2_348_810_240


def test_program_config_maps_the_file(config, cell):
    cfg = DRIVER.program_config(config, cell["max_len"])
    assert cfg.mixers == ("mla",) * 7 and cfg.ffn == ("dense",) + ("moe",) * 6
    assert (cfg.d_model, cfg.heads, cfg.d_ff, cfg.vocab, cfg.max_len) == (
        2048, 20, 10240, 154880, 32768)
    assert cfg.routed.held == 64 and cfg.routed.d_shared == 1536
    assert cfg.latent.q_rank == 768 and cfg.latent.gate is False
    with pytest.raises(ValueError, match="every expert is held"):
        DRIVER.program_config(dict(config, experts_held=[0, 32]), 32768)
    with pytest.raises(ValueError, match="disagree"):
        DRIVER.program_config(dict(config, num_hidden_layers=8), 32768)
    with pytest.raises(ValueError, match="rope_scaling"):
        DRIVER.program_config(dict(config, rope_scaling={"factor": 2}), 32768)


def test_latent_attention_by_hand():
    """Three tokens, two heads, every step written out in numpy: the query's
    two products around its norm, the shared rotated key, keys and values
    rebuilt from the normed latent, scores over sqrt(nope + rope), no gate."""
    rng = np.random.default_rng(0)
    D, H, rq, lat, nope, rope, dv = 6, 2, 4, 5, 3, 2, 4
    sizes = dict(num_attention_heads=H, qk_nope_head_dim=nope,
                 qk_rope_head_dim=rope, v_head_dim=dv, kv_lora_rank=lat,
                 rms_norm_eps=1e-5, rope_theta=100.0)
    shapes = dict(q_a=(D, rq), q_b=(rq, H * (nope + rope)),
                  kva=(D, lat + rope), kvb=(lat, H * (nope + dv)),
                  o=(H * dv, D))
    lp = {k: {"w": rng.normal(0, 1, s)} for k, s in shapes.items()}
    lp["q_norm"] = {"scale": rng.normal(1, 0.1, rq)}
    lp["c_norm"] = {"scale": rng.normal(1, 0.1, lat)}
    x = rng.normal(0, 1, (3, D))

    def rms(t, scale):
        return t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-5) * scale

    def rot(t, pos):                # rope 2: one pair, frequency 1
        c, s = np.cos(pos), np.sin(pos)
        return np.array([t[0] * c - t[1] * s, t[0] * s + t[1] * c])

    q = (rms(x @ lp["q_a"]["w"], lp["q_norm"]["scale"])
         @ lp["q_b"]["w"]).reshape(3, H, nope + rope)
    ckr = x @ lp["kva"]["w"]
    c = rms(ckr[:, :lat], lp["c_norm"]["scale"])
    kv = (c @ lp["kvb"]["w"]).reshape(3, H, nope + dv)
    out = np.zeros((3, H * dv))
    for t in range(3):
        for h in range(H):
            qr = rot(q[t, h, nope:], t)
            s = np.array([q[t, h, :nope] @ kv[u, h, :nope]
                          + qr @ rot(ckr[u, lat:], u) for u in range(t + 1)])
            s = s / np.sqrt(nope + rope)
            p = np.exp(s - s.max())
            out[t, h * dv:(h + 1) * dv] = (p / p.sum()) @ kv[:t + 1, h, nope:]
    import jax
    f32 = lambda tree: jax.tree.map(                          # noqa: E731
        lambda t: jnp.asarray(t, jnp.float32), tree)
    got = REFERENCE.mla(f32(x), f32(lp), sizes, lambda t: t)
    assert np.allclose(np.asarray(got), out @ lp["o"]["w"], atol=1e-4)


def test_routing_case_by_hand():
    """8 experts, top-2, x1.8: the bias changes the choice and not the
    weights, which sum to the scaling factor."""
    sizes = dict(num_experts_per_tok=2, routed_scaling_factor=1.8)
    logit = np.array([[2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0]], np.float32)
    bias = np.array([0, 0, 0, 0, 0, 0.5, 0, 0], np.float32)
    chosen, w = REFERENCE.route(jnp.asarray(logit), jnp.eye(8), bias, sizes,
                                lambda t: t)
    s = 1 / (1 + np.exp(-logit[0]))
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 5]
    order = np.asarray(chosen)[0]
    assert np.allclose(np.asarray(w)[0], 1.8 * s[order] / (s[0] + s[5]),
                       atol=1e-6)
    assert np.asarray(w).sum() == pytest.approx(1.8)


def test_window_flops_by_hand(config):
    # one layer, one head of 1 + 1 / 1 under a rank-1 latent: a key costs
    # 2 x (1 + 1) to rebuild, a pair 2 x (1 + 1 + 1)
    assert costs_glm.latent_window_flops(10, 7, 1, 1, 1, 1, 1, 1) \
        == 4 * 10 + 6 * 7
    # the issue's reckoning at published sizes: 128 lanes over 24k keys,
    # expanded, ~0.28 TFLOP a layer
    keys, lanes = 24_576, 128
    pairs = lanes * (keys - lanes) + lanes * (lanes + 1) // 2
    one = costs_glm.latent_window_flops(keys, pairs, 1, 20, 512, 192, 64, 256)
    assert 0.27e12 < one < 0.30e12
    assert costs_glm.latent_window_flops(keys, pairs, 7, 20, 512, 192, 64,
                                         256) == 7 * one


def test_the_readers_read_this_configuration(config, cell):
    """The accepted routed and latent readers and the two new ones on this
    configuration: 6 routed layers, 64 experts held, 18,874,368 bytes an
    expert, 7 latent layers of 1,152 B a cached position."""
    from benchmarks.layer_metrics import _routed
    assert _routed.routed_layers(config) == 6
    assert costs_moe.expert_bytes(config["hidden_size"],
                                  config["moe_intermediate_size"]) \
        == 18_874_368
    assert costs_moe.latent_decode_bytes([1], 7, 512, 64) == 7 * 1152
    # 100 ticks of 32 rows at 20,000 positions in the traced 4 s of a 40 s
    # window of 1,000; 20 windows of 128 lanes at offset 20,000 in the 4 s
    events = [(0.04 * i, 20_000) for i in range(1000) for _ in range(32)]
    touched = 1000 * 6 * 56
    keys, lanes = 20 * 20_128, 128
    pairs = 20 * (lanes * 20_000 + lanes * (lanes + 1) // 2)
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={},
                 modules={"jit_tick": [4.0, 100]},
                 module_ops={"jit_tick/_moe_experts_call": [0.8, 600],
                             "jit_tick/_pa_latent_call": [2.0, 700],
                             "jit_tick/while": [0.5, 140]})
    counters = dict(
        kv_stats={"attn_ticks_kernel": 1200, "prefill_chunks": 200,
                  "prefill_tokens": 25_600, "moe_experts_touched": touched},
        kv_stats_traced={"latent_window_context": keys,
                         "latent_window_pairs": pairs},
        token_events=events, traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=40.0,
        window_elapsed_s=40.0)

    def read(name):
        return run.load_by_path("layer_metrics", name).read(
            trace, counters, cell, config, PEAK)
    assert read("moe_experts_touched_pct.generate") == pytest.approx(
        100 * 56 / 64)
    assert read("moe_device_share_pct.generate") == pytest.approx(20.0)
    assert read("moe_expert_roofline") == pytest.approx(
        100 * (touched / 10 * 18_874_368 / 819e9) / 0.8)
    assert read("latent_attn_roofline") == pytest.approx(
        100 * (3200 * 20_000 * 7 * 1152 / 819e9) / 2.0)
    assert read("prefill_tokens_per_s.generate") == pytest.approx(640.0)
    assert read("latent_device_share_pct.generate") == pytest.approx(62.5)
    flops = costs_glm.latent_window_flops(keys, pairs, 7, 20, 512, 192, 64,
                                          256)
    assert read("latent_window_roofline") == pytest.approx(
        100 * (flops / 197e12) / 0.5)
    assert 0 < read("latent_window_roofline") < 100
    # no window in the stretch: the share is the kernel's alone, no roofline
    del trace["module_ops"]["jit_tick/while"]
    assert read("latent_device_share_pct.generate") == pytest.approx(50.0)
    assert read("latent_window_roofline") is None


def test_new_readers_return_none_where_the_program_counts_nothing(config,
                                                                   cell):
    """On a program that lacks the counters and the kernel (the parent
    commit), the new readers return None and do not raise."""
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={}, modules={},
                 module_ops={"jit_tick/fusion": [1.0, 10]})
    counters = dict(kv_stats={"attn_ticks_kernel": 10}, token_events=[],
                    traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=51.0,
                    window_elapsed_s=51.0)
    for name in ("latent_device_share_pct.generate",
                 "latent_window_roofline"):
        reader = run.load_by_path("layer_metrics", name)
        assert reader.read(trace, counters, cell, config, PEAK) is None
        assert reader.read(trace, {}, {}, {}, PEAK) is None


def test_the_cell_names_what_the_trace_shows(cell):
    assert set(cell["trace_ops"]) == {"latent_decode", "latent_window",
                                      "moe_experts", "moe_routing", "tick"}
    assert cell["driver"] == "generate_glm"
    assert (cell["slots"], cell["max_len"], cell["trace_seconds"]) == (
        32, 32768, 4)
    assert os.path.exists(os.path.join(run.HERE, "traffic",
                                       cell["traffic"] + ".json"))
    assert json.dumps(cell["limits"])
