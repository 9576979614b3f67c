"""Long-context attention bench: ring / Ulysses sequence parallelism.

The reference never scales sequence length (SURVEY §5 — it scales rows);
this framework's sequence-parallel kernels (`parallel/ring.py`) are the
beyond-parity capability. This bench measures attention wall-clock and the
max sequence length that fits, full (single-device) vs ring/Ulysses over a
sequence-sharded mesh. Prints one JSON line per config.

CPU smoke: BENCH_SCALE=small runs tiny shapes on the virtual 8-device mesh.
On hardware, the mesh axis rides ICI.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = os.environ.get("BENCH_SCALE", "") == "small"


def main():
    if SMALL:
        from mmlspark_tpu.utils.device import force_cpu
        jax = force_cpu(virtual_devices=8)
    else:
        import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mmlspark_tpu.ops.flash_attention import flash_attention
    from mmlspark_tpu.parallel.ring import (local_attention,
                                            plan_attention_impl,
                                            wrap_ring_attention)

    sp = 4 if SMALL else min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    B, H, D = (1, 4, 16) if SMALL else (1, 12, 64)
    seqs = [256, 512] if SMALL else [4096, 16384, 65536]
    # remote compiles at 64K take minutes each; let a driver scope a run
    if os.environ.get("BENCH_SEQS"):
        seqs = [int(s) for s in os.environ["BENCH_SEQS"].split(",")]
    impls = tuple(s.strip() for s in os.environ.get(
        "BENCH_IMPLS", "full,flash,ring,ring_flash,ulysses").split(",")
        if s.strip())
    unknown = set(impls) - {"full", "flash", "ring", "ring_flash", "ulysses"}
    if unknown:
        # an unvalidated name would silently fall through to the ulysses
        # branch and publish a mislabeled timing
        raise SystemExit(f"unknown BENCH_IMPLS {sorted(unknown)}")

    # HBM budget for the feasibility gate (0 disables). The O(S²) legs at
    # 16k-bwd/64k fail at COMPILE time on one chip — the r4/r5 campaigns
    # recorded those as opaque remote-compile HTTP 500s and re-paid the
    # doomed multi-minute compile every window. The planner (calibrated
    # against exactly those campaign outcomes) now classifies them up
    # front; the row says WHY and what would fit instead.
    if os.environ.get("BENCH_HBM_BYTES"):
        hbm = float(os.environ["BENCH_HBM_BYTES"])
    elif SMALL:
        hbm = 0.0
    else:
        try:  # the real per-device budget when the runtime exposes it
            hbm = float(jax.devices()[0].memory_stats()["bytes_limit"])
        except Exception:
            hbm = 16e9  # TPU v5e, where memory_stats is not reported

    def infeasible_verdict(impl, direction, S, sp):
        # hbm == 0 in SMALL mode unless BENCH_HBM_BYTES is set explicitly
        # (the explicit knob always wins — it is how the gate is driven
        # and CPU-tested without a chip)
        if not hbm:
            return None
        plan = plan_attention_impl(impl, direction, B, H, S,
                                   sp=sp, hbm_bytes=hbm)
        if plan["feasible"]:
            return None
        gb = plan["transient_bytes"] / 1e9
        fix = (f"feasible at sp>={plan['min_sp']}" if plan["min_sp"]
               else "no sp helps")
        return (f"infeasible: ~{gb:.3g} GB f32 scores > {hbm/1e9:.3g} GB "
                f"HBM at sp={sp} ({fix}; O(S) impls: flash/ring_flash)")

    def impl_fn_args(impl, q, k, v):
        """(fn, device args) per impl — ONE dispatch shared by the forward
        and backward timing loops so specs cannot drift between them."""
        if impl == "full":
            return local_attention, [jax.device_put(x) for x in (q, k, v)]
        if impl == "flash":
            # single-device Pallas streaming-softmax kernel: the O(S)
            # alternative when the score matrix no longer fits
            return (lambda a, b, c: flash_attention(a, b, c),
                    [jax.device_put(x) for x in (q, k, v)])
        sh = NamedSharding(mesh, P(None, None, "sp", None))
        return (wrap_ring_attention(mesh, "sp", impl=impl),
                [jax.device_put(x, sh) for x in (q, k, v)])

    rng = np.random.default_rng(0)
    for S in seqs:
        q = rng.normal(0, 1, (B, H, S, D)).astype(np.float32)
        k = rng.normal(0, 1, (B, H, S, D)).astype(np.float32)
        v = rng.normal(0, 1, (B, H, S, D)).astype(np.float32)
        results = {}
        full_out = None
        for impl in impls:
            verdict = infeasible_verdict(impl, "fwd", S,
                                         int(mesh.shape["sp"]))
            if verdict:
                results[impl] = verdict
                continue
            try:
                base_fn, args = impl_fn_args(impl, q, k, v)
                # tpulint: disable=TPU002 — one compile per (impl, S)
                # config is the benchmark design; shapes change every
                # iteration so no cache could be reused anyway
                fn = jax.jit(base_fn)
                # a fetched scalar is the completion fence; fetching only
                # the LAST of the dispatched calls fences all of them —
                # device programs run in order — so a single host round
                # trip amortizes over the repeats
                reps = 5
                # bind the output ONCE — two _f(*a) calls inside one jit
                # would run attention twice per rep unless XLA CSE merges
                # the inlined subgraphs, inflating ms/step up to 2x
                # tpulint: disable=TPU002 — compiled once per config, then
                # reused for all reps inside this iteration
                timed = jax.jit(
                    lambda *a, _f=fn: (lambda o: (
                        jnp.sum(o.astype(jnp.float32)), o))(_f(*a)))
                _, out = timed(*args)   # the one compile
                float(_)
                t0 = time.perf_counter()
                rs = [timed(*args)[0] for _ in range(reps)]
                float(rs[-1])
                results[impl] = round(
                    (time.perf_counter() - t0) / reps * 1e3, 2)
                if impl == "full":
                    full_out = np.asarray(out)
                elif full_out is not None:
                    # accuracy vs the already-computed full output — when
                    # full OOMs (the headline case: ring fits, full cannot)
                    # the sequence-parallel timings must survive
                    np.testing.assert_allclose(np.asarray(out), full_out,
                                               rtol=2e-3, atol=2e-3)
            except Exception as e:
                msg = (str(e).splitlines() or [repr(e)])[0][:80]
                results[impl] = f"error: {msg}"
        print(json.dumps({"metric": "long_context_attention_ms",
                          "seq_len": S, "heads": H, "head_dim": D,
                          "sp": int(mesh.shape["sp"]), **results,
                          # amortized-fence design: one window, mean of
                          # reps (per-rep fences would add ~RTT each)
                          "reps": 5, "timing": "mean-of-reps-single-fence",
                          "platform": jax.default_backend()}), flush=True)

        # --- backward: the flash bwd kernels vs XLA-differentiated dense.
        # (round-3 verdict: the bwd kernels had only ever run in interpret
        # mode; this times them on whatever backend is live.)
        if os.environ.get("BENCH_GRADS", "1") != "1":
            continue
        bwd, full_grads = {}, None
        for impl in impls:
            verdict = infeasible_verdict(impl, "bwd", S,
                                         int(mesh.shape["sp"]))
            if verdict:
                bwd[impl] = verdict
                continue
            try:
                # the sequence-parallel impls train too (ring-level VJP)
                base, args = impl_fn_args(impl, q, k, v)

                def loss(a, b, c, _f=base):
                    return jnp.sum(_f(a, b, c).astype(jnp.float32))

                # tpulint: disable=TPU002 — per-config compile by design
                gfn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                gs = gfn(*args)                      # the one compile
                float(jnp.sum(gs[0][0, 0, 0, :2].astype(jnp.float32)))
                reps = 3
                t0 = time.perf_counter()
                for _ in range(reps):
                    gs = gfn(*args)
                # fetched scalar depending on the LAST dispatch fences all
                float(jnp.sum(gs[2][0, 0, -1, :2].astype(jnp.float32)))
                bwd[impl] = round(
                    (time.perf_counter() - t0) / reps * 1e3, 2)
                if impl == "full":
                    full_grads = [np.asarray(g) for g in gs]
                elif full_grads is not None:
                    # accuracy is a SEPARATE verdict: a tolerance miss must
                    # not clobber a valid hardware timing with an "error:"
                    # string indistinguishable from a crash
                    try:
                        for g, fg in zip(gs, full_grads):
                            np.testing.assert_allclose(
                                np.asarray(g), fg, rtol=5e-3, atol=5e-3)
                        bwd[f"{impl}_grad_match"] = True
                    except AssertionError as e:
                        bwd[f"{impl}_grad_match"] = False
                        bwd[f"{impl}_grad_diff"] = \
                            (str(e).splitlines() or [""])[0][:80]
            except Exception as e:
                msg = (str(e).splitlines() or [repr(e)])[0][:80]
                bwd[impl] = f"error: {msg}"
        if bwd:
            print(json.dumps({"metric": "long_context_attention_bwd_ms",
                              "seq_len": S, "heads": H, "head_dim": D,
                              **bwd,
                              "platform": jax.default_backend()}),
                  flush=True)


if __name__ == "__main__":
    main()
