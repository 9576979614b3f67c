"""The shared-context state-space cell (``granite_docqa_shared32``) at sizes
a CPU can hold: a sound run is correct with every tick on the state-space
step and the grouped-query kernel, every admission a hit that shares its
context's pages and restores its snapshot, and no pair dropped; the control
is not; the configuration file against the catalog row's values and the cut's
arithmetic; the driver's mapping of every key; the traffic; the accepted
readers and the two new ones against counts made by hand."""

import json
import os

import numpy as np
import pytest

from benchmarks import costs_lfm2, costs_moe, costs_nemotron, idle_gaps
from benchmarks import run, traffic

from . import tiny

CELL = "granite_docqa_shared32"
REFERENCE = run.load_by_path("references", "granitemoehybrid")
DRIVER = run.load_by_path("drivers", "generate_granite")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4
         + (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 3)
#: the catalog row's ``config`` (model-configs guide, ``granite-4.0-h-small``),
#: as published
PUBLISHED = dict(
    attention_bias=False, attention_multiplier=0.0078125,
    embedding_multiplier=12, hidden_act="silu", hidden_size=4096,
    intermediate_size=768, layer_types=TYPES, logits_scaling=16,
    mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_head=64, mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
    mamba_n_heads=128, mamba_proj_bias=False, max_position_embeddings=131072,
    model_type="granitemoehybrid", normalization_function="rmsnorm",
    num_attention_heads=32, num_experts_per_tok=10, num_hidden_layers=40,
    num_key_value_heads=8, num_local_experts=72,
    position_embedding_type="nope", residual_multiplier=0.22,
    rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    shared_intermediate_size=1536, tie_word_embeddings=True,
    vocab_size=100352)

# one group of heads, a 16-wide router top-3 in 2 shares of 8 (a share under
# 8 experts is refused), three layers: mamba, attention, mamba
tiny.SHRINK["generate_granite"] = dict(
    config=dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
                head_dim=8, attention_multiplier=0.125, mamba_n_heads=16,
                mamba_d_head=8, mamba_d_state=16, mamba_n_groups=1,
                mamba_chunk_size=8, intermediate_size=24,
                shared_intermediate_size=48, num_local_experts=8,
                experts_held=[0, 8], published=dict(num_local_experts=16),
                num_experts_per_tok=3, vocab_size=256,
                layer_types=["mamba", "full_attention", "mamba"],
                layers_held=[0, 1, 2], num_hidden_layers=3,
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
    return run.load_json(run.HERE, "configs", "granite4_h_small_ep2_l10.json")


@pytest.fixture(scope="module")
def cell():
    return run.load_json(run.HERE, "workloads", f"{CELL}.json")


@pytest.fixture(scope="module")
def sound():
    return tiny.run_cell(CELL, seed=2147483999, seconds=2.0)


def test_sound_run_is_correct_and_every_admission_restores_and_shares(sound):
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
                 "gqa_ticks_missing", "prefix_misses_in_window",
                 "shared_tokens_short_of_the_contexts",
                 "admissions_without_a_restored_snapshot",
                 "prefix_hits_missing", "failed_requests",
                 "streamed_unequal_to_final", "page_allocations_failed",
                 "compiles_in_window", "served_token_gap_mean",
                 "served_token_gap_max"):
        assert name in compared and compared[name]["limit"] is not None
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
    moved = [ln["samples"]["counters_moved"] for ln in before
             if "samples" in ln][0]
    assert moved["prefix_hits"] > 0 == moved["prefix_misses"]
    assert moved["state_snapshots_restored"] == moved["prefix_hits"]
    # a snapshot at this size: two ssm layers' state and tails
    assert moved["state_snapshot_bytes_restored"] == moved["prefix_hits"] \
        * 2 * (8 * 16 * 16 + 3 * 160) * 4
    assert moved["prefix_tokens_shared"] == moved["prefix_hit_tokens"] > 0
    assert moved["prefill_tokens"] > 0 < moved["attn_ticks_ssm"]
    assert moved["ssm_state_rows"] % 2 == 0 and moved["ssm_state_rows"] > 0
    assert 0 < moved["moe_pairs_held"] < moved["moe_pairs_routed"]
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
    assert control["served_token_gap_mean"] > 0.01
    assert control["served_token_gap_mean"] \
        >= 3 * sound["served_token_gap_mean"], (sound, control)
    # the counts of the traced stretch (1 s of the 2) are read apart
    traced = result["counters"]["kv_stats_traced"]
    whole = result["counters"]["kv_stats"]
    assert 0 < traced["attn_ticks_kernel"] < whole["attn_ticks_kernel"]
    assert set(DRIVER.POOL_COUNTS) | {"prefix_hits"} <= set(traced)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_the_published_value(config, key):
    """Every key of the row at its published value, but the four the cut
    changes, which ``reduced`` lists and ``published`` keeps."""
    if key in config["reduced"]:
        assert config["published"][key] == PUBLISHED[key]
        assert config[key] != PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key]


def test_the_cut_is_the_first_period_and_half_of_the_experts(config):
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_local_experts", "vocab_size"]
    assert (TYPES.count("mamba"), TYPES.count("attention")) == (36, 4)
    assert [i for i, k in enumerate(TYPES) if k == "attention"] \
        == [5, 15, 25, 35]
    assert config["layers_held"] == list(range(10))
    # the readers' spelling of the published word, nothing else changed
    assert [{"full_attention": "attention"}.get(k, k)
            for k in config["layer_types"]] == TYPES[:10]
    assert config["num_hidden_layers"] == 10
    assert config["experts_held"] == [0, 36]
    assert config["num_local_experts"] * 2 == PUBLISHED["num_local_experts"]
    assert config["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    # the guide's floors: a whole period, 8 experts, an eighth of the ids
    assert config["num_local_experts"] >= 8
    assert config["deployment"]["pipeline_stages"] == 4
    assert config["deployment"]["chips_sharing_a_layer"] == 2
    for key in ("published", "deployment", "assumed", "departures"):
        assert config[key]
    assert config["departures"]["mathematics"] == "none"
    # what the accepted readers read under other names
    assert (config["mamba_num_heads"], config["mamba_head_dim"],
            config["ssm_state_size"], config["moe_intermediate_size"],
            config["head_dim"]) == (
        config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"], config["intermediate_size"],
        config["hidden_size"] // config["num_attention_heads"])


def test_the_cuts_arithmetic(config):
    """ISSUE 49's count: 4,757M parameters, 9.51 GB in bf16."""
    D, F, Fs, V = 4096, 768, 1536, 50176
    inner, conv = 128 * 64, 128 * 64 + 2 * 128
    held = config["deployment"]["parameters_held"]
    assert held["expert"] == 3 * D * F == 9_437_184
    assert held["mamba_mixer"] == (D * (2 * inner + 2 * 128 + 128)
                                   + inner * D + conv * 5 + 3 * 128 + inner
                                   + D) == 102_291_072
    assert held["attention_mixer"] == 2 * D * D + 2 * D * 1024 + D
    assert held["feed_forward_outside_experts"] == D * 72 + 3 * D * Fs + D
    assert held["experts_36"] == 36 * held["expert"]
    rest = held["feed_forward_outside_experts"] + held["experts_36"]
    assert held["mamba_layer"] == held["mamba_mixer"] + rest == 461_203_072
    assert held["attention_layer"] == held["attention_mixer"] + rest
    assert held["ten_layers"] == 9 * held["mamba_layer"] \
        + held["attention_layer"] == 4_551_686_784
    total = held["ten_layers"] + V * D + D
    assert held["total"] == total == 4_757_211_776
    assert held["bytes_bf16"] == 2 * total
    # a layer whole is 1.6 GB, so ten do not fit; above a quarter of the
    # chip before a state or a page is allocated
    assert 2 * (held["mamba_mixer"] + held["feed_forward_outside_experts"]
                + 72 * held["expert"]) > 1.6e9
    assert 2 * total > 0.25 * 16.9e9


def test_program_config_maps_every_key(config, cell):
    cfg = DRIVER.program_config(config, cell["max_len"])
    assert cfg.mixers == ("ssm",) * 5 + ("gqa",) + ("ssm",) * 4
    assert cfg.ffn == ("moe",) * 10
    assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.vocab,
            cfg.max_len, cfg.layers) == (4096, 32, 8, 128, 50176, 65536, 10)
    assert cfg.ssm == (128, 64, 128, 1, 4, 256)
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.groups, r.scale,
            r.d_expert, r.d_shared, r.latent, r.form, r.score) == (
        72, 0, 36, 10, 1, 1.0, 768, 1536, 0, "swiglu", "softmax")
    assert (cfg.attn_scale, cfg.embed_scale, cfg.residual_scale,
            cfg.logit_scale) == (1 / 128, 12.0, 0.22, 1 / 16)
    assert cfg.norm_eps == 1e-5 and cfg.qk_positions is False
    assert cfg.tied_head is True and cfg.norm == "rmsnorm"
    for change in (dict(experts_held=[0, 72]), dict(num_hidden_layers=9)):
        with pytest.raises(ValueError, match="disagree"):
            DRIVER.program_config(dict(config, **change), 65536)
    for change in (dict(position_embedding_type="rope"),
                   dict(hidden_act="gelu"), dict(mamba_expand=3),
                   dict(mamba_conv_bias=False), dict(attention_bias=True)):
        with pytest.raises(ValueError, match="published form"):
            DRIVER.program_config(dict(config, **change), 65536)
    with pytest.raises(ValueError, match="layer_types"):
        DRIVER.program_config(dict(config, layer_types=["mlp"] * 10), 65536)


def test_the_mix_is_the_issues(cell, config):
    mix = traffic.load(cell["traffic"])
    assert cell["traffic"] == "docs8x4_c24k-52k_q128_o192"
    assert (mix["clients"], mix["requests_per_client"],
            mix["callers_per_document"]) == (32, 8, 4)
    assert mix["documents"] == dict(count=8, shortest=24576, step=4096)
    assert mix["prompt"] == dict(median=128, sigma=0.7, min=32, max=512)
    assert mix["output"] == dict(median=192, sigma=0.5, min=64, max=512)
    assert (mix["ramp_seconds"], mix["start_stagger_s"],
            mix["check_requests"], mix["max_total"]) == (10, 0.1, 2, 1024)
    lengths = sorted(24576 + 4096 * i for i in range(8))
    assert lengths[-1] == 53248 and all(n % 256 == 0 for n in lengths)
    assert sum(lengths) == 311_296
    assert lengths[-1] + mix["max_total"] == mix["max_total_with_context"] \
        == 54272 <= cell["max_len"]
    docs = DRIVER.docs.documents(dict(mix, documents=dict(
        count=8, shortest=64, step=8)), 7, config["vocab_size"])
    assert sorted(len(d) for _, d in docs) == [64 + 8 * i for i in range(8)]
    plans = traffic.closed_loop_requests(mix, 2147483999,
                                         config["vocab_size"])
    sent = [(len(p), o) for plan in plans for p, o in plan]
    assert len(plans) == 32 and len(sent) == 256
    assert all(32 <= n <= 512 and 64 <= o <= 512 and n + o <= 1024
               for n, o in sent)
    assert all(0 < t < 50176 for plan in plans for p, _ in plan for t in p)
    chunk = cell["engine"]["prefill_chunk"]
    assert chunk == 512 >= max(n for n, _ in sent)

    def width(n):
        return max(64, 1 << (n - 1).bit_length())
    assert {width(n) for n, _ in sent} \
        <= {width(n) for n in mix["warm"]["questions"]}
    # the pool holds the working set: 1,216 stored pages, 4 a live row, trash
    pages = cell["engine"]["kv_pages"]
    assert sum(n // 256 for n in lengths) == 1216
    assert 1216 + 32 * 4 + 1 <= pages == 1400
    assert (cell["slots"], cell["max_len"], cell["trace_seconds"]) == (
        32, 65536, 4)
    # the warm-up's defragmentation is provoked: four long requests retire
    # under a short one, past the pool's threshold of a quarter
    d = mix["warm"]["defrag"]
    below = sum(-(-(n + o) // 256) for n, o in zip(d["prompts"][:-1],
                                                 d["outputs"][:-1]))
    assert below >= pages // 4 + 16


def test_the_reference_routes_by_hand():
    """16 logits, the 3 largest, a softmax over them alone."""
    import jax.numpy as jnp
    logit = np.array([[2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0] + [0.0] * 8],
                     np.float32)
    chosen, w = REFERENCE.route(jnp.asarray(logit), jnp.eye(16),
                                dict(num_experts_per_tok=3), lambda t: t)
    assert np.asarray(chosen).tolist() == [[0, 4, 5]]
    e = np.exp(np.array([2.0, 1.5, 1.4]) - 2.0)
    assert np.allclose(np.asarray(w)[0], e / e.sum(), atol=1e-6)


def test_the_readers_read_this_configuration(config, cell, monkeypatch):
    """The accepted state-space, routed and grouped-query readers and the two
    new ones on this configuration: TEN routed layers of 36 held experts of
    28.3 MB, NINE ssm layers of 8.39 MB a live row a step, one
    ``full_attention`` layer of 8 KV heads of 128 = 4 KB a cached token."""
    from benchmarks.layer_metrics import _routed
    assert _routed.routed_layers(config) == 10
    assert config["layer_types"].count("full_attention") == 1
    assert costs_moe.expert_bytes(4096, 768) == 18_874_368
    assert costs_nemotron.ssm_state_bytes(1, 128, 64, 128) == 8_388_608
    assert costs_lfm2.gqa_decode_bytes([1], 1, 8, 128) == 4096
    # 100 ticks of 32 rows at 40,000 positions in the traced 4 s of a 40 s
    # window of 1,000
    events = [(0.04 * i, 40_000) for i in range(1000) for _ in range(32)]
    touched = 1000 * 10 * 35
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={},
                 modules={"jit_tick": [3.2, 100]},
                 module_ops={"jit_tick/_moe_experts_call": [1.0, 1000],
                             "jit_tick/_ssm_step_call": [0.4, 900],
                             "jit_tick/_pa_gqa_call": [0.8, 100]})
    counters = dict(
        kv_stats={"attn_ticks_kernel": 1200, "prefill_chunks": 200,
                  "prefill_tokens": 25_600, "moe_experts_touched": touched,
                  "ssm_state_rows": 1000 * 9 * 32},
        token_events=events, traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=40.0,
        window_elapsed_s=40.0)

    def read(name):
        return run.load_by_path("layer_metrics", name).read(
            trace, counters, cell, config, PEAK)
    assert read("moe_experts_touched_pct.generate") == pytest.approx(
        100 * 35 / 36)
    assert read("moe_device_share_pct.generate") == pytest.approx(31.25)
    assert read("ssm_device_share_pct.generate") == pytest.approx(12.5)
    assert read("gqa_device_share_pct.generate") == pytest.approx(25.0)
    assert read("moe_expert_roofline") == pytest.approx(
        100 * (touched / 10 * 18_874_368 / 819e9) / 1.0)
    assert read("ssm_state_roofline") == pytest.approx(
        100 * (100 * 9 * 32 * 8_388_608 / 819e9) / 0.4)
    assert read("gqa_attn_roofline") == pytest.approx(
        100 * (3200 * 40_000 * 4096 / 819e9) / 0.8)
    assert read("prefill_tokens_per_s.generate") == pytest.approx(640.0)
    for name in ("moe_expert_roofline", "ssm_state_roofline",
                 "gqa_attn_roofline"):
        assert 0 < read(name) < 100
    # the restores of the stretch [1 s, 5 s): three hits opened in it (the
    # last cut by its end), one before it and one after it not counted
    ms = 1_000_000
    spans = {7: [("decoder.step", 0, 6000 * ms),
                 ("decoder.tick", 100 * ms, 200 * ms),
                 ("decoder.state_restore", 900 * ms, 1100 * ms),
                 ("decoder.state_restore", 2000 * ms, 2003 * ms),
                 ("decoder.state_restore", 3000 * ms, 3005 * ms),
                 ("decoder.state_restore", 4998 * ms, 5010 * ms),
                 ("decoder.state_restore", 5500 * ms, 5503 * ms)]}
    found = dict(threads=spans, stretch=(1000 * ms, 5000 * ms))
    monkeypatch.setattr(idle_gaps, "analysis", lambda *a: found)
    assert read("state_restore_ms_per_hit.generate") == pytest.approx(
        (3 + 5 + 2) / 3)
    # a ring that wrapped into the stretch gives None, as does no hit
    monkeypatch.setattr(idle_gaps, "analysis", lambda *a: dict(
        threads={7: spans[7][3:]}, stretch=(1000 * ms, 5000 * ms)))
    assert read("state_restore_ms_per_hit.generate") is None
    monkeypatch.setattr(idle_gaps, "analysis", lambda *a: dict(
        threads={7: spans[7][:2]}, stretch=(1000 * ms, 5000 * ms)))
    assert read("state_restore_ms_per_hit.generate") is None


def test_new_readers_return_none_where_the_program_counts_nothing(
        config, cell, monkeypatch):
    """On a program that lacks the kernel or the span (or a run without a
    trace), the new readers return None and do not raise."""
    trace = dict(devices=1, window_s=4.0, busy_s=3.9, ops={}, modules={},
                 module_ops={"jit_tick/fusion": [1.0, 10]})
    counters = dict(kv_stats={"attn_ticks_kernel": 10}, token_events=[],
                    traced=dict(t0=0.0, t1=4.0), t0=0.0, t1=51.0,
                    window_elapsed_s=51.0)
    monkeypatch.setattr(idle_gaps, "analysis", lambda *a: None)
    for name in ("gqa_device_share_pct.generate",
                 "state_restore_ms_per_hit.generate"):
        reader = run.load_by_path("layer_metrics", name)
        assert reader.read(trace, counters, cell, config, PEAK) is None
        assert reader.read(trace, {}, {}, {}, PEAK) is None


def test_the_manifest_holds_the_new_entries(cell):
    assert set(cell["trace_ops"]) == {"ssm_decode", "moe_experts",
                                      "moe_routing", "gqa_decode", "tick"}
    assert cell["driver"] == "generate_granite"
    assert os.path.exists(os.path.join(run.HERE, "traffic",
                                       cell["traffic"] + ".json"))
    assert json.dumps(cell["limits"])
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 8
    assert manifest["configs"][-1]["name"] == "granite4_h_small_ep2_l10"
    assert manifest["workloads"][-1]["name"] == CELL
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    lists = [name for name, m in by_name.items()
             if CELL in m.get("workloads", ())]
    assert {"ssm_state_roofline", "ssm_device_share_pct.generate",
            "moe_expert_roofline", "moe_device_share_pct.generate",
            "moe_experts_touched_pct.generate", "gqa_attn_roofline",
            "prefill_tokens_per_s.generate", "host_ms_per_tick.generate",
            "gqa_device_share_pct.generate",
            "state_restore_ms_per_hit.generate"} <= set(lists)
    assert by_name["gqa_device_share_pct.generate"]["workloads"] == [
        CELL, "lfm2_ragchat_closed32", "nemotronsuper_chat_closed32"]
    assert by_name["state_restore_ms_per_hit.generate"]["workloads"] == [
        CELL, "sala_docqa_closed8"]
    assert by_name["state_restore_ms_per_hit.generate"]["layer"] \
        == "scheduler"
    # the two cells the share is also read in name the kernel it reads
    for other in by_name["gqa_device_share_pct.generate"]["workloads"]:
        ops = run.load_json(run.HERE, "workloads", f"{other}.json")[
            "trace_ops"]
        assert ops["gqa_decode"] == "^jit_tick/_pa_gqa_call$"
    # the accepted latent-expert reader counts a non-gated expert in a latent
    assert "latent_moe_expert_roofline" not in lists
    assert CELL in manifest["end_to_end"][1]["workloads"]
