"""Decoder (LLM) serving throughput: prefill and KV-cached decode.

Beyond reference parity — SynapseML has no autoregressive serving story at
all (its deep-learning module is batch ONNX inference,
``deep-learning/.../onnx/ONNXModel.scala:305-355``). A TPU-native framework
needs one: this bench measures the two phases every LLM-serving stack is
judged on, on the native zoo decoder (``models/zoo/transformer.py``):

* **prefill** — one batched causal forward over the prompt,
  ``transformer_apply``; compute-bound, rides the MXU.
* **decode** — ``lax.scan`` over ``decode_step`` with the static-shape
  KV-cache updated in place via ``dynamic_update_slice``; one compiled
  program serves the whole loop (no per-token dispatch), the TPU answer to
  ORT's GroupQueryAttention decode loop.

Prints one JSON line per phase. Sized by env: BENCH_DECODE_B (batch),
BENCH_DECODE_P (prompt len), BENCH_DECODE_T (new tokens),
BENCH_SCALE=small for CPU-friendly shapes. All timings fenced by fetched
scalars.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = os.environ.get("BENCH_SCALE", "") == "small"


def _env_int(name, default):
    return int(os.environ.get(name, default))


def main():
    if SMALL:
        from mmlspark_tpu.utils.device import force_cpu
        jax = force_cpu()
    else:
        import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.zoo.transformer import (
        TransformerConfig, decode_step, init_kv_cache, init_transformer,
        transformer_apply)
    from mmlspark_tpu.utils.device import is_tpu

    if SMALL or not is_tpu():
        cfg = TransformerConfig(vocab=1024, layers=4, d_model=256, heads=8,
                                d_ff=1024, max_len=256, causal=True,
                                norm="rmsnorm", position="rope")
        B, P, T = 4, 32, 32
    else:
        # GPT-2-small-class decoder (Llama-style: RMSNorm + RoPE), bf16
        cfg = TransformerConfig(vocab=32000, layers=12, d_model=768,
                                heads=12, d_ff=3072, max_len=2048,
                                causal=True, norm="rmsnorm",
                                position="rope")
        B, P, T = 32, 128, 128
    B = _env_int("BENCH_DECODE_B", B)
    P = _env_int("BENCH_DECODE_P", P)
    T = _env_int("BENCH_DECODE_T", T)

    params = init_transformer(cfg, seed=0)
    params = jax.device_put(jax.tree.map(jnp.asarray, params))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, P), dtype=np.int32))

    # sweep mode: skip straight to the continuous-batching row (each
    # skipped section is an extra remote compile per sweep point)
    cb_only = os.environ.get("BENCH_CB_ONLY", "0") == "1"

    if not cb_only:
        # ---- prefill: one causal forward over the prompt ----
        @jax.jit
        def prefill(params, ids):
            h = transformer_apply(params, ids, cfg)
            return h[:, -1].astype(jnp.float32) @ params["lm_head"]["w"]

        logits = prefill(params, prompt)                   # compile
        float(jnp.sum(logits))                             # fence
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(jnp.sum(prefill(params, prompt)))
            best = min(best, time.perf_counter() - t0)
        prefill_tps = B * P / best
        print(json.dumps({
            "metric": "decoder_prefill_tokens_per_sec",
            "value": round(prefill_tps, 1), "unit": "tokens/sec/chip",
            "batch": B, "prompt_len": P,
            "params_m": round(n_params / 1e6, 1),
            "ms": round(best * 1e3, 2),
            "platform": jax.default_backend()}), flush=True)

        # ---- decode: whole loop as ONE compiled scan over decode_step ----
        L = P + T
        cache0 = init_kv_cache(cfg, B, L)

        @jax.jit
        def decode(params, first_tok, cache):
            def step(carry, t):
                tok, cache = carry
                logits, cache = decode_step(params, tok, P + t, cache, cfg)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt, cache), None

            (tok, cache), _ = jax.lax.scan(step, (first_tok, cache),
                                           jnp.arange(T))
            return tok

        first = prompt[:, -1]
        tok = decode(params, first, cache0)                # compile
        float(jnp.sum(tok))                                # fence
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(jnp.sum(decode(params, first, cache0)))
            best = min(best, time.perf_counter() - t0)
        decode_tps = B * T / best
        print(json.dumps({
            "metric": "decoder_cached_decode_tokens_per_sec",
            "value": round(decode_tps, 1), "unit": "tokens/sec/chip",
            "batch": B, "new_tokens": T, "kv_len": L,
            "params_m": round(n_params / 1e6, 1),
            "ms_per_token": round(best * 1e3 / T, 3),
            "platform": jax.default_backend()}), flush=True)

    # ---- continuous batching: staggered requests through the slot pool ----
    from mmlspark_tpu.serving.continuous import ContinuousDecoder

    n_req = _env_int("BENCH_DECODE_REQS", 2 * B)
    # k decode steps per dispatch: every dispatch pays a host round trip
    # defaults from an earlier round's on-chip sweep (in a record deleted
    # in PR 23; not re-measured): k=16 ≈ 1.5× k=8 at every measured depth (best 4,265
    # vs 2,888 tok/s) and k=32 bought nothing more; at k=8 depth is
    # monotone harmful (retirement lag), while the k=16 d=1-vs-d=2
    # ordering is within-window noise — d=2 kept as the engine default.
    k_steps = _env_int("BENCH_CB_STEPS", 16)
    cb_depth = _env_int("BENCH_CB_DEPTH", 2)
    # prefill-ahead: stage the next wave's prefills while the pool is
    # full, so wave boundaries pay one insert dispatch instead of
    # prefill + a first-token round-trip (default: one full wave)
    cb_ahead = _env_int("BENCH_CB_AHEAD", B)
    eng = ContinuousDecoder(params, cfg, max_slots=B, max_len=P + T + 1,
                            steps_per_dispatch=k_steps,
                            pipeline_depth=cb_depth,
                            prefill_ahead=cb_ahead)
    rng2 = np.random.default_rng(1)
    # warm the steady-state program set: a full-pool burst compiles the
    # max-size prefill bucket, the power-of-two insert chunks, and the
    # ragged tick — first-time remote compiles are minutes of wall clock
    # that must not land inside the timed region (the r5 campaign caught
    # a 23 s in-run stall from exactly this)
    warm = [eng.submit(rng2.integers(0, cfg.vocab, P), max_new_tokens=2)
            for _ in range(B)]
    while not all(w.done for w in warm):
        eng.step()
    reqs = [eng.submit(rng2.integers(0, cfg.vocab, P), max_new_tokens=T)
            for _ in range(n_req)]
    t0 = time.perf_counter()
    while not all(r.done for r in reqs):
        eng.step()
    dt = time.perf_counter() - t0
    total_toks = sum(len(r.tokens) for r in reqs)
    ttft = [r.first_token_at - r.submitted_at for r in reqs]
    print(json.dumps({
        "metric": "decoder_continuous_batching_tokens_per_sec",
        "value": round(total_toks / dt, 1), "unit": "tokens/sec/chip",
        "slots": B, "requests": n_req, "prompt_len": P, "new_tokens": T,
        "steps_per_dispatch": k_steps, "pipeline_depth": cb_depth,
        "prefill_ahead": cb_ahead,
        "staged_prefills": eng.stats.get("staged_prefills", 0),
        "ttft_p50_ms": round(1e3 * sorted(ttft)[len(ttft) // 2], 1),
        "ttft_max_ms": round(1e3 * max(ttft), 1),
        "platform": jax.default_backend()}), flush=True)

    if cb_only:
        return  # sweep mode: just the continuous-batching row

    # -- speculative decoding: draft-then-verify vs plain cached greedy --
    from mmlspark_tpu.models.zoo.speculative import generate_speculative_fused as generate_speculative
    from mmlspark_tpu.models.zoo.transformer import generate_cached
    d_cfg = cfg._replace(layers=max(1, cfg.layers // 4),
                         d_model=cfg.d_model // 2, heads=cfg.heads // 2,
                         d_ff=cfg.d_ff // 2)
    d_params = init_transformer(d_cfg, seed=1)
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab, (1, P)))
    gamma = _env_int("BENCH_SPEC_GAMMA", 4)
    # warm + check output parity (exact in fp32; under bf16 near-tie
    # argmaxes can flip between the window and step compositions, so the
    # fraction is reported rather than asserted)
    ref = generate_cached(params, prompt, cfg, max_new_tokens=T,
                          temperature=0.0)
    spec, stats = generate_speculative(params, d_params, prompt, cfg,
                                       d_cfg, max_new_tokens=T, gamma=gamma)
    match_frac = float((np.asarray(ref) == np.asarray(spec)).mean())
    t0 = time.perf_counter()
    int(np.asarray(generate_cached(params, prompt, cfg, max_new_tokens=T,
                                   temperature=0.0))[0, -1])   # fence
    plain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, stats = generate_speculative(params, d_params, prompt, cfg, d_cfg,
                                    max_new_tokens=T, gamma=gamma)
    spec_s = time.perf_counter() - t0
    # perfect-draft upper bound: draft == target, acceptance == gamma —
    # what the machinery delivers when the draft is good
    generate_speculative(params, params, prompt, cfg, cfg,
                         max_new_tokens=T, gamma=gamma)       # warm
    t0 = time.perf_counter()
    _, ub = generate_speculative(params, params, prompt, cfg, cfg,
                                 max_new_tokens=T, gamma=gamma)
    ub_s = time.perf_counter() - t0
    print(json.dumps({
        "metric": "decoder_speculative_tokens_per_sec",
        "value": round(T / spec_s, 1), "unit": "tokens/sec/chip",
        "plain_tokens_per_sec": round(T / plain_s, 1),
        "speedup_random_draft": round(plain_s / spec_s, 2),
        "speedup_perfect_draft": round(plain_s / ub_s, 2),
        "gamma": gamma,
        "acceptance_per_round": round(
            stats["accepted_drafts"] / max(stats["rounds"], 1), 2),
        "target_forwards": stats["target_forwards"],
        "perfect_draft_target_forwards": ub["target_forwards"],
        "greedy_match_frac": round(match_frac, 4),
        "platform": jax.default_backend()}), flush=True)

    # -- speculative with a DISTILLED draft: the configuration the feature
    # exists for. The target first trains on a low-entropy synthetic
    # language (markov_sampler — zero-egress stand-in for natural text,
    # which is likewise far below vocab-uniform entropy), then a 2-layer
    # draft distills from the frozen target; acceptance and the wall-clock
    # speedup are reported on prompts from that language. Random-weight
    # rows above stay for continuity — they measure pure machinery cost.
    if os.environ.get("BENCH_SPEC_DISTILL", "1") == "1":
        from mmlspark_tpu.models.zoo.distill import (distill_draft,
                                                     markov_sampler,
                                                     train_lm)
        from mmlspark_tpu.models.zoo.speculative import \
            generate_speculative_fused
        t_steps = _env_int("BENCH_SPEC_TRAIN_STEPS", 30 if SMALL else 200)
        d_steps = _env_int("BENCH_SPEC_DISTILL_STEPS", 30 if SMALL else 300)
        bt = 4 if SMALL else 16
        batch_fn = markov_sampler(cfg.vocab, batch=bt, seq=min(P, 64),
                                  seed=5)
        t0 = time.perf_counter()
        t_trained, _ = train_lm(params, cfg, batch_fn, steps=t_steps,
                                learning_rate=3e-4)
        dd_cfg = cfg._replace(layers=2, d_model=cfg.d_model // 2,
                              heads=max(2, cfg.heads // 2),
                              d_ff=cfg.d_ff // 2)
        dd_params, _ = distill_draft(t_trained, cfg, dd_cfg, batch_fn,
                                     steps=d_steps, learning_rate=1e-3)
        train_s = time.perf_counter() - t0
        mk_prompt = jnp.asarray(batch_fn(777)[:1, :P].astype(np.int32))
        ref = generate_cached(t_trained, mk_prompt, cfg, max_new_tokens=T,
                              temperature=0.0)
        spec, dstats = generate_speculative_fused(
            t_trained, dd_params, mk_prompt, cfg, dd_cfg,
            max_new_tokens=T, gamma=gamma)
        d_match = float((np.asarray(ref) == np.asarray(spec)).mean())
        plain_ts, spec_ts = [], []
        for _ in range(3):               # interleaved best-of
            t0 = time.perf_counter()
            int(np.asarray(generate_cached(
                t_trained, mk_prompt, cfg, max_new_tokens=T,
                temperature=0.0))[0, -1])                      # fence
            plain_ts.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            _, dstats = generate_speculative_fused(
                t_trained, dd_params, mk_prompt, cfg, dd_cfg,
                max_new_tokens=T, gamma=gamma)
            spec_ts.append(time.perf_counter() - t0)
        print(json.dumps({
            "metric": "decoder_speculative_distilled_tokens_per_sec",
            "value": round(T / min(spec_ts), 1), "unit": "tokens/sec/chip",
            "plain_tokens_per_sec": round(T / min(plain_ts), 1),
            "speedup_distilled_draft": round(min(plain_ts) / min(spec_ts),
                                             2),
            "best_of": 3,
            "pass_spread": round((max(spec_ts) - min(spec_ts))
                                 / max(spec_ts), 3),
            "gamma": gamma,
            "acceptance_per_round": round(
                dstats["accepted_drafts"] / max(dstats["rounds"], 1), 2),
            "target_forwards": dstats["target_forwards"],
            "greedy_match_frac": round(d_match, 4),
            "train_steps": t_steps, "distill_steps": d_steps,
            "train_plus_distill_sec": round(train_s, 1),
            "draft_layers": 2, "draft_d_model": dd_cfg.d_model,
            "platform": jax.default_backend()}), flush=True)

        # -- speculative CONTINUOUS BATCHING: the distilled draft inside
        # the slot pool (per-slot accept via decode_window_ragged). The
        # plain-engine control runs the SAME trained target on the SAME
        # markov-language prompts, so the row reads as: what does
        # drafting buy a saturated serving pool. Outputs are asserted
        # request-identical between the two engines.
        if os.environ.get("BENCH_CB_SPEC", "1") == "1":
            spec_k = _env_int("BENCH_CB_SPEC_STEPS", 4 if SMALL else 8)
            n_req2 = _env_int("BENCH_DECODE_REQS", 2 * B)
            # one trained target + distilled draft serve every swept
            # gamma — a per-gamma retrain would cost ~80 s of window each
            gammas = [int(g) for g in os.environ.get(
                "BENCH_CB_SPEC_GAMMAS", str(gamma)).split(",")
                if g.strip()] or [gamma]
            prompts2 = [np.asarray(batch_fn(1000 + i)[0, :P], np.int32)
                        for i in range(n_req2)]

            def run_cb(with_draft, g=gamma):
                eng = ContinuousDecoder(
                    t_trained, cfg, max_slots=B, max_len=P + T + 1,
                    steps_per_dispatch=spec_k if with_draft else k_steps,
                    pipeline_depth=cb_depth, prefill_ahead=cb_ahead,
                    draft_params=dd_params if with_draft else None,
                    draft_cfg=dd_cfg if with_draft else None,
                    gamma=g)
                warm2 = [eng.submit(p, max_new_tokens=2)
                         for p in prompts2[:B]]
                while not all(w.done for w in warm2):
                    eng.step()
                reqs2 = [eng.submit(p, max_new_tokens=T)
                         for p in prompts2]
                t0 = time.perf_counter()
                while not all(r.done for r in reqs2):
                    eng.step()
                dt = time.perf_counter() - t0
                return (sum(len(r.tokens) for r in reqs2) / dt,
                        [tuple(r.tokens) for r in reqs2], eng.stats)

            plain_tps, plain_out, _ = run_cb(False)
            for g in gammas:
                spec_tps, spec_out, st = run_cb(True, g)
                assert spec_out == plain_out, \
                    "speculative pool diverged from the plain engine"
                acc = (st.get("spec_emitted", 0)
                       / max(st.get("spec_round_slots", 1), 1))
                print(json.dumps({
                    "metric":
                        "decoder_continuous_batching_spec_tokens_per_sec",
                    "value": round(spec_tps, 1),
                    "unit": "tokens/sec/chip",
                    "plain_tokens_per_sec": round(plain_tps, 1),
                    "speedup": round(spec_tps / plain_tps, 2),
                    "outputs_match": spec_out == plain_out,
                    "slots": B, "requests": n_req2, "prompt_len": P,
                    "new_tokens": T, "gamma": g,
                    "rounds_per_dispatch": spec_k,
                    "tokens_per_round_slot": round(acc, 2),
                    "pipeline_depth": cb_depth,
                    "prefill_ahead": cb_ahead,
                    "platform": jax.default_backend()}), flush=True)


if __name__ == "__main__":
    main()
