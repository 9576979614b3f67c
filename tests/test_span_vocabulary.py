"""The span vocabulary (docs/observability.md): every name fires on a CPU
run of the transform path (``ONNXModel`` over ``BatchRunner``) and of a tiny
``GenerationEngine``; a streamed request's trace ends when the stream
closes and carries the request's timeline; a span outside a trace and a
profile allocates nothing but its row of the span log."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.observability import tracing as tr

TRANSFORM_SPANS = ["ONNXModel.transform", "partition", "runner.run",
                   "runner.next", "runner.coerce", "runner.pad",
                   "runner.h2d", "runner.dispatch", "runner.d2h",
                   "onnx.collect", "frame.concat"]
GENERATION_SPANS = ["engine.admit_http", "engine.pump_streams",
                    "engine.reply_finished", "engine.idle", "decoder.step",
                    "decoder.admit", "decoder.tick",
                    "decoder.stage_prefills", "decoder.compact",
                    "continuous.prefill", "continuous.prefill_chunk",
                    "continuous.drain", "decoder.account", "decoder.retire"]
#: a hybrid decoder's prefix is pages plus a state snapshot
HYBRID_SPANS = ["decoder.state_snapshot", "decoder.state_restore"]


def _stream(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for line in r:
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
    return events


@pytest.fixture(scope="module")
def transform_run():
    """One two-partition transform under a request trace: the names the
    span log saw, and the trace's spans."""
    import mmlspark_tpu.onnx as O
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel
    rng = np.random.default_rng(0)
    graph = O.make_graph(
        [O.make_node("MatMul", ["x", "w"], ["logits"])], "linear",
        inputs=[O.make_tensor_value_info("x", np.float32, ["N", 8])],
        outputs=[O.make_tensor_value_info("logits", np.float32, ["N", 3])],
        initializers={"w": rng.normal(0, 0.5, (8, 3)).astype(np.float32)})
    model = ONNXModel(O.make_model(graph), feed_dict={"x": "feats"},
                      fetch_dict={"logits": "logits"}, pin_devices=False,
                      mini_batch_size=4)
    X = rng.normal(0, 1, (16, 8)).astype(np.float32)
    df = DataFrame({"feats": list(X)}, npartitions=2)
    tr._SPAN_LOG.clear()
    root = tr.start_trace("transform")
    with tr.activate(root):
        out = model.transform(df)
    root.end()
    assert len(out) == 16
    return {name for name, *_ in tr.span_log()}, root.trace.spans


@pytest.fixture(scope="module")
def generation_run():
    """On the decoder before the engine's thread starts, a short request
    retires under a long one (the pool compacts: no race to lose); then
    three streamed requests on two slots over HTTP: one long enough to
    prefill in chunks, a third that waits for a slot (prefill-ahead)."""
    from mmlspark_tpu.models.zoo.transformer import (TransformerConfig,
                                                     init_transformer)
    from mmlspark_tpu.serving.generation import GenerationEngine
    cfg = TransformerConfig(vocab=128, layers=2, d_model=64, heads=4,
                            d_ff=128, max_len=64, causal=True,
                            norm="rmsnorm", position="rope",
                            dtype=jnp.float32)
    tr._SPAN_LOG.clear()
    tr.get_flight_recorder().clear()
    rng = np.random.default_rng(3)
    jobs = [(5, 3), (20, 24), (6, 4)]
    replies = {}
    eng = GenerationEngine(init_transformer(cfg, seed=0), cfg, max_slots=2,
                           max_len=48, page_size=4, prefill_chunk=8,
                           prefill_ahead=1)
    eng.decoder._defrag_thr = 1
    pair = [eng.decoder.submit(rng.integers(1, cfg.vocab, n).astype(np.int32),
                               m) for n, m in ((5, 3), (9, 24))]
    while not all(t.done for t in pair):
        eng.decoder.step()
    with eng:
        def client(i, n, m):
            prompt = [int(t) for t in rng.integers(1, cfg.vocab, n)]
            replies[i] = _stream(eng.address, {"tokens": prompt,
                                               "max_new": m})
        threads = [threading.Thread(target=client, args=(i, n, m))
                   for i, (n, m) in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        # the loop's idle round comes once nothing is in flight: on a loaded
        # machine the engine would otherwise be stopped before it
        deadline = time.monotonic() + 30.0
        while (time.monotonic() < deadline and
               not any(name == "engine.idle" for name, *_ in tr.span_log())):
            time.sleep(0.01)
    assert all(replies[i][-1].get("done") for i in range(len(jobs)))
    return ({name for name, *_ in tr.span_log()},
            tr.get_flight_recorder().traces())


@pytest.fixture(scope="module")
def hybrid_run():
    """A tiny hybrid decoder (one sparse-attention layer, one
    linear-attention layer): a prefix registered by a miss, then a hit. The
    names the span log saw and what the pool and its registry counted."""
    from mmlspark_tpu import observability as obs
    from mmlspark_tpu.models.zoo.transformer import (SparseAttention,
                                                     TransformerConfig,
                                                     init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    cfg = TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=2, d_ff=64, max_len=96,
        causal=True, norm="rmsnorm", position="rope", dtype=jnp.float32,
        mixers=("sparse", "lightning"), kv_heads=1, head_dim=16,
        sparse=SparseAttention(kernel_size=4, kernel_stride=2, block_size=8,
                               topk=4, window_size=8, init_blocks=1,
                               dense_len=32))
    tr._SPAN_LOG.clear()
    before = obs.snapshot()
    dec = ContinuousDecoder(init_transformer(cfg, seed=0), cfg, max_slots=2,
                            max_len=96, page_size=8, prefill_chunk=16)
    rng = np.random.default_rng(5)
    doc = rng.integers(1, cfg.vocab, 40).astype(np.int32)
    for n in (6, 9):                            # a miss, then a hit
        req = dec.submit(np.concatenate(
            [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)]), 4,
            prefix_key="doc", prefix_len=40)
        while not req.done:
            dec.step()
        assert req.error is None
    dec._alloc_with_pressure(dec._kv.num_pages - 1 - dec._kv.pages_in_use
                             + 1)               # pressure evicts the prefix
    return ({name for name, *_ in tr.span_log()}, dec._kv.stats,
            before, obs.snapshot())


@pytest.mark.parametrize("name", TRANSFORM_SPANS + GENERATION_SPANS
                         + HYBRID_SPANS)
def test_span_fires(name, request):
    run = ("transform_run" if name in TRANSFORM_SPANS else
           "hybrid_run" if name in HYBRID_SPANS else "generation_run")
    names, *_ = request.getfixturevalue(run)
    assert name in names


@pytest.mark.parametrize("event", ["stored", "restored", "evicted"])
def test_state_snapshot_counters(hybrid_run, event):
    """One snapshot stored, restored and evicted, with its bytes (one
    lightning layer, 2 heads of 16 x 16 float32, and one row of compressed
    keys, 1 KV head x 96 / 2 entries of 16 float32), in the pool's stats and
    in the registry."""
    _, stats, before, after = hybrid_run
    assert stats[f"state_snapshots_{event}"] == 1
    assert stats[f"state_snapshot_bytes_{event}"] == 2048 + 48 * 16 * 4

    def series(snap, name):
        return sum(s["value"] for s in snap.get(name, {}).get("series", ())
                   if s["labels"].get("event") == event)
    for name, want in (("mmlspark_kvpool_state_snapshots_total", 1),
                       ("mmlspark_kvpool_state_snapshot_bytes_total", 5120)):
        assert series(after, name) - series(before, name) == want


@pytest.mark.parametrize("label", ["sparse", "dense"])
def test_attn_ticks_are_labelled_by_path(hybrid_run, label):
    """Beside ``kernel`` / ``gather``: the prefill chunks and ticks under
    ``dense_len`` (32) count ``dense``, those past it ``sparse``."""
    _, stats, before, after = hybrid_run
    assert stats[f"attn_ticks_{label}"] > 0
    assert stats["attn_ticks_sparse"] + stats["attn_ticks_dense"] \
        == stats["attn_ticks_kernel"]

    def series(snap):
        return sum(s["value"] for s in snap.get(
            "mmlspark_kvpool_kernel_ticks_total", {}).get("series", ())
            if s["labels"].get("impl") == label)
    assert series(after) - series(before) == stats[f"attn_ticks_{label}"]


@pytest.mark.parametrize("gauge,stat", [
    ("mmlspark_kvpool_select_walk_pages", "select_walk_pages"),
    ("mmlspark_kvpool_select_walk_steps", "select_walk_steps")])
def test_select_walk_gauges_hold_the_pools_counts(hybrid_run, gauge, stat):
    """The selected-block kernel's walk, counted a tick from the rows'
    positions: one sparse layer with one KV head, lists of ``topk`` = 4 pages
    walked four a grid step (``select_block``), so a tick's two rows take a
    step each and its one live row, past ``dense_len``, lists four pages."""
    _, stats, _, after = hybrid_run
    assert stats["select_walk_pages"] == 2 * stats["select_walk_steps"] > 0
    assert [s["value"] for s in after[gauge]["series"]] == [stats[stat]]


#: what a routed decoder's tick counts of itself (pool ``stats["moe_.."]`` and
#: ``mmlspark_kvpool_moe_total{count=..}``), and the labels its two mixers'
#: kernels add to ``mmlspark_kvpool_kernel_ticks_total``
MOE_COUNTERS = ["pairs_routed", "pairs_held", "pairs_dropped",
                "pairs_misplaced", "experts_touched", "tiles",
                "product_steps", "expert_load_max"]
ROUTED_TICK_LABELS = ["kda", "latent"]


@pytest.fixture(scope="module")
def routed_run():
    """A tiny routed decoder (a kda layer under a dense feed-forward, an mla
    layer under 8 held of 16 routed experts): two requests, one a registered
    prefix. What the pool and the registry counted."""
    from mmlspark_tpu import observability as obs
    from mmlspark_tpu.models.zoo.transformer import (
        DeltaRule, LatentAttention, RoutedExperts, TransformerConfig,
        init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    cfg = TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=2, d_ff=64, max_len=96,
        causal=True, norm="rmsnorm", position="rope", dtype=jnp.float32,
        mixers=("kda", "mla"), head_dim=16, ffn=("dense", "moe"),
        routed=RoutedExperts(experts=16, first=0, count=8, per_token=2,
                             groups=2, groups_kept=1, scale=2.5, d_expert=16,
                             d_shared=16),
        latent=LatentAttention(latent=16, nope=8, rope=8, value=8),
        kda=DeltaRule())
    before = obs.snapshot()
    dec = ContinuousDecoder(init_transformer(cfg, seed=0), cfg, max_slots=2,
                            max_len=96, page_size=8, prefill_chunk=16)
    rng = np.random.default_rng(5)
    doc = rng.integers(1, cfg.vocab, 24).astype(np.int32)
    reqs = [dec.submit(np.concatenate(
        [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)]), 5,
        prefix_key="doc", prefix_len=24) for n in (6, 9)]
    while not all(r.done for r in reqs):
        dec.step()
    assert all(r.error is None for r in reqs)
    return dec._kv.stats, before, obs.snapshot()


@pytest.mark.parametrize("count", MOE_COUNTERS)
def test_routing_counters_ride_out_with_the_tokens(routed_run, count):
    stats, before, after = routed_run

    def series(snap):
        return sum(s["value"] for s in snap.get(
            "mmlspark_kvpool_moe_total", {}).get("series", ())
            if s["labels"].get("count") == count)
    assert series(after) - series(before) == stats[f"moe_{count}"]
    if count in ("pairs_dropped", "pairs_misplaced"):
        assert stats[f"moe_{count}"] == 0
    else:
        assert stats[f"moe_{count}"] > 0
    # two experts a token, one routed layer: a decode step of n live rows
    # routes 2 n pairs, at most 2 rows a step; a step counts ALL the rows it
    # routed, so a prefill window that rode a tick adds 2 a token of its own
    assert stats["prefill_chunks_riding"] > 0
    assert stats["moe_pairs_held"] <= stats["moe_pairs_routed"] \
        <= 2 * (2 * stats["attn_ticks_kda"] + stats["prefill_tokens"])
    # an expert with a pair has a tile, a run of its tiles is one grid step:
    # the 16-lane windows that rode a tick put at most two tiles on an expert
    assert stats["moe_experts_touched"] <= stats["moe_product_steps"] \
        <= stats["moe_tiles"] <= 2 * stats["moe_product_steps"]


@pytest.mark.parametrize("label", ROUTED_TICK_LABELS)
def test_kda_and_latent_ticks_are_labelled(routed_run, label):
    stats, before, after = routed_run
    assert stats[f"attn_ticks_{label}"] \
        == stats["attn_ticks_kernel"] - stats["prefill_chunks"] > 0
    assert f"attn_ticks_{label}_window" not in stats

    def series(snap):
        return sum(s["value"] for s in snap.get(
            "mmlspark_kvpool_kernel_ticks_total", {}).get("series", ())
            if s["labels"].get("impl") == label)
    assert series(after) - series(before) == stats[f"attn_ticks_{label}"]


def test_snapshot_bytes_count_the_convolution_tails(routed_run):
    """One kda layer: 2 heads of 16 x 16 float32 and 3 rows of 3 x 32
    float32 tails."""
    stats, *_ = routed_run
    assert stats["state_snapshot_bytes_stored"] == 2 * 16 * 16 * 4 \
        + 3 * 96 * 4


#: the labels a conv + grouped-query decoder's tick adds to
#: ``mmlspark_kvpool_kernel_ticks_total``, and the prompt tokens its chunk
#: windows computed (pool ``stats["prefill_tokens"]``,
#: ``mmlspark_kvpool_prefill_tokens_total``)
CONV_GQA_TICK_LABELS = ["conv", "gqa"]


@pytest.fixture(scope="module")
def conv_gqa_run():
    """A tiny conv + grouped-query decoder (a conv layer under a dense
    feed-forward, a gqa layer under 8 routed experts all held): two
    requests one after the other, the first registering a prefix the second
    restores. What the pool and the registry counted."""
    from mmlspark_tpu import observability as obs
    from mmlspark_tpu.models.zoo.transformer import (
        RoutedExperts, ShortConv, TransformerConfig, init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    cfg = TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=4, kv_heads=2, d_ff=64,
        max_len=96, causal=True, norm="rmsnorm", position="rope",
        dtype=jnp.float32, mixers=("conv", "gqa"), head_dim=8,
        ffn=("dense", "moe"), conv=ShortConv(taps=3), norm_eps=1e-5,
        routed=RoutedExperts(experts=8, per_token=2, d_expert=16))
    before = obs.snapshot()
    dec = ContinuousDecoder(init_transformer(cfg, seed=0), cfg, max_slots=2,
                            max_len=96, page_size=8, prefill_chunk=16)
    rng = np.random.default_rng(5)
    doc = rng.integers(1, cfg.vocab, 24).astype(np.int32)
    prompts = [np.concatenate(
        [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)])
        for n in (6, 9)]
    for p in prompts:                       # a miss, then a hit
        req = dec.submit(p, 5, prefix_key="doc", prefix_len=24)
        while not req.done:
            dec.step()
        assert req.error is None
    return dec._kv.stats, before, obs.snapshot(), prompts


@pytest.mark.parametrize("label", CONV_GQA_TICK_LABELS)
def test_conv_and_gqa_ticks_are_labelled(conv_gqa_run, label):
    stats, before, after, _ = conv_gqa_run
    assert stats[f"attn_ticks_{label}"] \
        == stats["attn_ticks_kernel"] - stats["prefill_chunks"] > 0
    assert "attn_ticks_gqa_window" not in stats

    def series(snap):
        return sum(s["value"] for s in snap.get(
            "mmlspark_kvpool_kernel_ticks_total", {}).get("series", ())
            if s["labels"].get("impl") == label)
    assert series(after) - series(before) == stats[f"attn_ticks_{label}"]


def test_prefill_tokens_count_what_the_chunk_windows_computed(conv_gqa_run):
    """The first request prefills whole (30 tokens: the prefix's 24 in two
    chunks, then 6); the second restores the prefix and computes its 9."""
    stats, before, after, prompts = conv_gqa_run
    assert stats["prefill_tokens"] == len(prompts[0]) + 9

    def total(snap):
        return sum(s["value"] for s in snap.get(
            "mmlspark_kvpool_prefill_tokens_total", {}).get("series", ()))
    assert total(after) - total(before) == stats["prefill_tokens"]


def test_snapshot_bytes_are_a_conv_layers_tails_alone(conv_gqa_run):
    """One conv layer: 2 rows of 32 float32 values; the gqa layer keeps
    nothing a slot."""
    stats, *_ = conv_gqa_run
    assert stats["state_snapshot_bytes_stored"] == 2 * 32 * 4


#: what a state-space decoder adds: tick labels ``ssm`` and ``gqa`` (pool
#: ``stats["attn_ticks_<label>"]``, ``mmlspark_kvpool_kernel_ticks_total
#: {impl=<label>}``) and the states its decode steps moved (pool
#: ``stats["ssm_state_rows"]``, the gauge ``mmlspark_kvpool_ssm_state_rows``)
SSM_TICK_LABELS = ["ssm", "gqa"]


@pytest.fixture(scope="module")
def ssm_run():
    """A tiny state-space decoder (a plain gqa layer at 16 query heads a KV
    head under 8 relu^2 experts in a latent, an ssm layer with no
    feed-forward): two requests one after the other, the first registering a
    prefix the second restores. What the pool and the registry counted."""
    from mmlspark_tpu import observability as obs
    from mmlspark_tpu.models.zoo.transformer import (
        RoutedExperts, StateSpace, TransformerConfig, init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    cfg = TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=16, kv_heads=1, d_ff=64,
        max_len=96, causal=True, norm="rmsnorm", position="rope",
        dtype=jnp.float32, mixers=("gqa", "ssm"), head_dim=8,
        ffn=("moe", "none"), norm_eps=1e-5, qk_positions=False,
        ssm=StateSpace(heads=4, head_dim=8, state=16, groups=2, taps=4,
                       chunk=8),
        routed=RoutedExperts(experts=8, per_token=3, scale=5.0, d_expert=24,
                             d_shared=16, latent=16, form="relu2"))
    before = obs.snapshot()
    dec = ContinuousDecoder(init_transformer(cfg, seed=0), cfg, max_slots=2,
                            max_len=96, page_size=8, prefill_chunk=16)
    rng = np.random.default_rng(5)
    doc = rng.integers(1, cfg.vocab, 24).astype(np.int32)
    for n in (6, 9):                        # a miss, then a hit
        req = dec.submit(np.concatenate(
            [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)]), 5,
            prefix_key="doc", prefix_len=24)
        while not req.done:
            dec.step()
        assert req.error is None
    return dec._kv.stats, before, obs.snapshot()


@pytest.mark.parametrize("label", SSM_TICK_LABELS)
def test_ssm_and_plain_gqa_ticks_are_labelled(ssm_run, label):
    stats, before, after = ssm_run
    assert stats[f"attn_ticks_{label}"] \
        == stats["attn_ticks_kernel"] - stats["prefill_chunks"] > 0
    assert f"attn_ticks_{label}_window" not in stats

    def series(snap):
        return sum(s["value"] for s in snap.get(
            "mmlspark_kvpool_kernel_ticks_total", {}).get("series", ())
            if s["labels"].get("impl") == label)
    assert series(after) - series(before) == stats[f"attn_ticks_{label}"]


def test_the_gauge_holds_the_states_the_steps_moved(ssm_run):
    """One ssm layer, one live row a tick: a state a tick."""
    stats, _, after = ssm_run
    assert stats["ssm_state_rows"] == stats["attn_ticks_ssm"] > 0
    series = after["mmlspark_kvpool_ssm_state_rows"]["series"]
    assert [s["value"] for s in series] == [stats["ssm_state_rows"]]


def test_snapshot_bytes_count_the_state_and_its_tails(ssm_run):
    """One ssm layer: 4 heads of (8 x 16) float32 and 3 rows of 32 + 2 x 2 x
    16 float32 values; the gqa layer keeps nothing a slot. Stored once,
    restored once."""
    stats, *_ = ssm_run
    row = (4 * 8 * 16 + 3 * (32 + 64)) * 4
    assert stats["state_snapshot_bytes_stored"] == row
    assert stats["state_snapshot_bytes_restored"] == row


#: what a decoder whose every layer is latent attention adds: the keys its
#: prefill windows attended over (pool ``stats["latent_window_keys"]``, the
#: gauge ``mmlspark_kvpool_latent_window_keys``), the tokens of stored prefix
#: pages its requests took by reference (``stats["prefix_tokens_shared"]``,
#: ``mmlspark_kvpool_prefix_tokens_shared``), and the keys under a window on
#: its span (``continuous.prefill_chunk``'s ``context=``)
LATENT_GAUGES = [("mmlspark_kvpool_latent_window_keys", "latent_window_keys"),
                 ("mmlspark_kvpool_prefix_tokens_shared",
                  "prefix_tokens_shared"),
                 ("mmlspark_kvpool_latent_sweep_pages", "latent_sweep_pages"),
                 ("mmlspark_kvpool_latent_sweep_steps", "latent_sweep_steps")]


@pytest.fixture(scope="module")
def latent_run():
    """A tiny all-latent decoder (5 heads of 12 + 8 / 16 under a rank-24
    query, a dense and a routed feed-forward with a shared expert): a
    request registers a prefix of 24 tokens (three pages of 8), two more take
    it by reference at once, all under one request trace. What the pool and
    the registry counted, and the trace's spans."""
    from mmlspark_tpu import observability as obs
    from mmlspark_tpu.models.zoo.transformer import (
        LatentAttention, RoutedExperts, TransformerConfig, init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    cfg = TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=5, d_ff=64, max_len=96,
        causal=True, norm="rmsnorm", position="rope", dtype=jnp.float32,
        mixers=("mla", "mla"), ffn=("dense", "moe"), norm_eps=1e-5,
        latent=LatentAttention(latent=32, nope=12, rope=8, value=16,
                               q_rank=24, gate=False),
        routed=RoutedExperts(experts=8, per_token=2, d_expert=16,
                             d_shared=16, scale=1.8))
    dec = ContinuousDecoder(init_transformer(cfg, seed=0), cfg, max_slots=2,
                            max_len=96, page_size=8, prefill_chunk=16)
    rng = np.random.default_rng(5)
    doc = rng.integers(1, cfg.vocab, 24).astype(np.int32)
    prompts = [np.concatenate(
        [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)])
        for n in (6, 9, 11)]
    tr._SPAN_LOG.clear()
    root = tr.start_trace("latent")
    with tr.activate(root):
        for group in (prompts[:1], prompts[1:]):    # a miss, then two hits
            reqs = [dec.submit(p, 5, prefix_key="doc", prefix_len=24)
                    for p in group]
            while not all(r.done for r in reqs):
                dec.step()
            assert all(r.error is None for r in reqs)
    root.end()
    return dec, obs.snapshot(), root.trace.spans


@pytest.mark.parametrize("gauge,stat", LATENT_GAUGES)
def test_latent_gauges_hold_the_pools_counts(latent_run, gauge, stat):
    dec, snap, _ = latent_run
    stats = dec._kv.stats
    # windows of 16, 8 and 6 lanes (the miss) and of 9 and 11 (the hits), a
    # tile the slot's 12 pages; two hits x three whole pages of 8 tokens
    # the absorbed kernel's sweep, counted a tick from the rows' lengths: two
    # mla layers, a slot's 12 pages a page a step, an idle row one step
    want = {"latent_window_keys": 5 * 96, "prefix_tokens_shared": 2 * 24,
            "latent_sweep_pages": stats["latent_sweep_pages"],
            "latent_sweep_steps": stats["latent_sweep_steps"]}
    assert want["latent_sweep_steps"] >= want["latent_sweep_pages"] \
        >= 2 * 4 * stats["attn_ticks_latent"]
    assert stats[stat] == want[stat]
    assert [s["value"] for s in snap[gauge]["series"]] == [want[stat]]
    assert stats["latent_window_context"] == 16 + 24 + 30 + 33 + 35
    assert dec.stats["prefix_hit_tokens"] == stats["prefix_tokens_shared"]


def test_a_window_says_the_keys_under_it(latent_run):
    _, _, spans = latent_run
    chunks = [s for s in spans if s.name == "continuous.prefill_chunk"]
    assert [(s.attrs["offset"], s.attrs["tokens"], s.attrs["context"])
            for s in chunks] == [(0, 16, 16), (16, 8, 24), (24, 6, 30),
                                 (24, 9, 33), (24, 11, 35)]
    assert all("riding" in s.attrs for s in chunks)


def test_a_prefix_of_pages_alone_opens_no_state_span(latent_run):
    """Nothing a slot: the hits restore no snapshot and the registration
    takes none; the tick's label is the absorbed kernel's."""
    dec, _, spans = latent_run
    names = {s.name for s in spans} | {n for n, *_ in tr.span_log()}
    assert not names & set(HYBRID_SPANS)
    stats = dec._kv.stats
    assert not any(k.startswith("state_snapshot") for k in stats)
    assert stats["attn_ticks_latent"] \
        == stats["attn_ticks_kernel"] - stats["prefill_chunks"] > 0


def test_transform_spans_join_the_request_trace(transform_run):
    _, spans = transform_run
    names = [s.name for s in spans]
    # both partitions, and the worker-thread stages under them
    assert names.count("partition") == 2 and names.count("runner.run") == 2
    dispatch = [s for s in spans if s.name == "runner.dispatch"]
    assert len(dispatch) == 4 and all("compiled" in s.attrs
                                      for s in dispatch)
    assert "cache_hit" not in [e["name"] for s in spans for e in s.events]


def test_streamed_trace_ends_at_close_with_the_timeline(generation_run):
    _, traces = generation_run
    roots = [t.root for t in traces if t.root.attrs.get("streaming")]
    assert len(roots) == 3
    for root in roots:
        a = root.attrs
        assert a["status"] == 200
        stamps = [a["submitted_at"], a["admitted_at"], a["first_token_at"],
                  a["finished_at"]]
        assert stamps == sorted(stamps)
        # the root covers the whole generation, not just the stream's open
        assert root.duration >= a["finished_at"] - a["submitted_at"]
        assert a["new_tokens"] in (3, 24, 4) and a["prompt_tokens"] >= 5
        events = [e["name"] for e in root.events]
        assert events.index("admitted") < events.index("first_token")


def test_replied_requests_keep_their_timelines(generation_run):
    """What each root span closed with is also on the engine's own list,
    whole, whatever the flight recorder kept."""
    from mmlspark_tpu.serving import generation
    _, traces = generation_run
    kept = generation.recent_timelines()
    assert len(kept) < generation.RECENT_TIMELINES
    for root in (t.root for t in traces if t.root.attrs.get("streaming")):
        mine = [a for a in kept
                if a["submitted_at"] == root.attrs["submitted_at"]]
        assert len(mine) == 1
        assert all(root.attrs[k] == v for k, v in mine[0].items())


def test_generation_timeline_histograms(generation_run):
    from mmlspark_tpu import observability as obs
    snap = obs.snapshot()
    for name in ("mmlspark_generation_queue_wait_seconds",
                 "mmlspark_generation_ttft_seconds"):
        assert snap[name]["series"][0]["count"] >= 3


def test_span_outside_trace_and_profile_allocates_no_span(monkeypatch):
    made = []
    real = tr.Span.__init__

    def counting(self, *a, **kw):
        made.append(self)
        real(self, *a, **kw)
    monkeypatch.setattr(tr.Span, "__init__", counting)
    tr._SPAN_LOG.clear()
    with tr.span("orphan", detail="x") as child:
        assert child is None and tr.current_span() is None
    assert made == []
    (name, thread, t0, t1), = tr.span_log()
    assert name == "orphan"
    assert thread == threading.get_ident() and t0 <= t1
