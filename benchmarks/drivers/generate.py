"""Token generation behind ``GenerationEngine``'s HTTP endpoint, closed loop:
one client thread per slot, each posting its next request (``"stream": true``)
when its last one ends. Clients start in set-up and have reached a steady
state when the window opens; the window counts what arrives inside it, and
requests still running when it closes are drained, not counted, not failed.
Every time is the client's own clock."""

import gc
import http.client
import json
import threading
import time
import urllib.parse

import numpy as np

from benchmarks import traffic
from benchmarks.layer_metrics._common import percentile


class Client(threading.Thread):
    """One caller: posts, reads the Server-Sent-Events reply line by line,
    stamps every event as it arrives, posts the next."""

    def __init__(self, url, plan, closing, start_delay):
        super().__init__(daemon=True)
        u = urllib.parse.urlparse(url)
        self.host, self.port, self.path = u.hostname, u.port, u.path or "/"
        self.plan, self.closing, self.start_delay = plan, closing, start_delay
        self.records = []

    def run(self):
        time.sleep(self.start_delay)
        i = 0
        while not self.closing.is_set():
            prompt, want = self.plan[i % len(self.plan)]
            i += 1
            self.records.append(self.one(prompt, want))

    def one(self, prompt, want):
        rec = dict(prompt=prompt, want=want, events=[], streamed=[],
                   tokens=None, error=None, t_done=None)
        body = json.dumps({"tokens": [int(t) for t in prompt],
                           "max_new": want, "stream": True})
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        try:
            rec["t_send"] = time.perf_counter()
            conn.request("POST", self.path, body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}"
                return rec
            while True:
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data:"):
                    continue
                now = time.perf_counter()
                event = json.loads(line[5:])
                if "error" in event:
                    rec["error"] = str(event["error"])
                elif event.get("done"):
                    rec["tokens"] = event["tokens"]
                    rec["t_done"] = now
                elif event.get("tokens"):
                    rec["events"].append((now, len(event["tokens"])))
                    rec["streamed"].extend(event["tokens"])
        except Exception as exc:        # a dropped connection is a failure
            rec["error"] = f"{type(exc).__name__}: {exc}"
        finally:
            conn.close()
        if rec["tokens"] is None and rec["error"] is None:
            rec["error"] = "stream ended without a final event"
        return rec


class Driver:
    def __init__(self, cell, config, seed, reference):
        import jax.numpy as jnp

        from mmlspark_tpu.models.zoo.transformer import TransformerConfig
        from mmlspark_tpu.serving.generation import GenerationEngine
        self.cell, self.config, self.seed, self.ref = (
            cell, config, seed, reference)
        self.mix = traffic.load(cell["traffic"])
        self.params = reference.make_weights(config, seed)
        cfg = TransformerConfig(
            vocab=config["vocab_size"], layers=config["n_layer"],
            d_model=config["n_embd"], heads=config["n_head"],
            d_ff=config["n_inner"], max_len=config["n_positions"],
            causal=True, dtype=jnp.dtype(config["compute_dtype"]),
            **config["program"])
        self.plan = traffic.closed_loop_requests(self.mix, seed,
                                                 config["vocab_size"])
        # every choice of implementation left at the engine's default
        # (a cell's file may name options; the cells of this PR name none)
        self.engine = GenerationEngine(
            self.params, cfg, max_slots=cell["slots"],
            max_len=cell["max_len"], reply_timeout=600.0,
            **cell.get("engine", {}))
        self.closing = threading.Event()
        self.clients = []
        self.records = []

    def counters(self):
        """The scheduler's and the pool's own counts, from the names they
        publish them under."""
        from mmlspark_tpu.serving import kv_pool as pool
        counts = dict(
            attn_ticks_kernel=pool.M_KERNEL_TICKS.labels(impl="kernel"),
            attn_ticks_gather=pool.M_KERNEL_TICKS.labels(impl="gather"),
            prefill_chunks=pool.M_PREFILL_CHUNKS.labels(),
            defrag_moves=pool.M_DEFRAG_MOVES.labels(),
            alloc_failures=pool.M_ALLOC_FAILURES.labels())
        return dict({k: int(c.get()) for k, c in counts.items()},
                    prefill_groups=self.engine.decoder.stats["prefills"])

    def drive(self, rng, requests):
        """Hand the decoder ``(prompt length, output length)`` requests all
        at once and step it until they are done: one admission, so the
        prompts that pad alike prefill as one group. Only before the
        engine's own thread exists."""
        decoder = self.engine.decoder
        tickets = [decoder.submit(
            rng.integers(1, self.config["vocab_size"], n).astype(np.int32),
            m) for n, m in requests]
        while not all(t.done for t in tickets):
            decoder.step()
        for t in tickets:
            decoder.result(t)           # raises what the request raised

    def warm(self):
        """Every program the traffic can reach, run once on the decoder
        (which has no warm-up of its own): each batched-prefill shape, a
        group size by a padded prompt length; each chunked-prefill window;
        the pool's defragmentation, provoked as traffic provokes it, by long
        requests that retire under a short one. Then the engine's thread and
        the closed loop itself for ``ramp_seconds``: the window opens on a
        steady state, not on every caller arriving at once."""
        rng = np.random.default_rng(self.seed + 2)
        w = self.mix["warm"]
        for n in w["plain_prompts"]:
            for k in w["group_sizes"]:
                self.drive(rng, [(n, 2)] * k)
        for n in w["chunked_prompts"]:
            self.drive(rng, [(n, 2)])
        moves = self.counters()["defrag_moves"]
        self.drive(rng, list(zip(w["defrag"]["prompts"],
                                 w["defrag"]["outputs"])))
        if self.counters()["defrag_moves"] == moves:
            raise RuntimeError("warm-up did not reach the pool's "
                               "defragmentation: the traffic file's "
                               "warm.defrag no longer provokes it")
        self.engine.start()
        self.clients = [
            Client(self.engine.address, plan, self.closing,
                   i * self.mix["start_stagger_s"])
            for i, plan in enumerate(self.plan)]
        for c in self.clients:
            c.start()
        time.sleep(self.mix["ramp_seconds"])

    def window(self, seconds):
        before = self.counters()
        t0 = time.perf_counter()
        time.sleep(seconds)
        t1 = time.perf_counter()
        after = self.counters()
        self.closing.set()
        for c in self.clients:
            c.join(timeout=600)
        if any(c.is_alive() for c in self.clients):
            raise RuntimeError("a client did not finish its last request")
        self.engine.stop()
        self.engine = None
        self.records = [r for c in self.clients for r in c.records]
        ok = [r for r in self.records if r["error"] is None]
        sent = [r for r in self.records if t0 <= r["t_send"] < t1]
        self.sent_ok = [r for r in sent if r["error"] is None]
        ttft = [r["events"][0][0] - r["t_send"] for r in self.sent_ok]
        tokens = sum(n for r in ok for t, n in r["events"] if t0 <= t < t1)
        gaps, token_events = [], []
        for r in ok:
            times = [t for t, _ in r["events"]]
            gaps += [b - a for a, b in zip(times, times[1:]) if t0 <= b < t1]
            # the tick that emitted output token j read the prompt and the
            # j tokens before it; token 0 comes out of the prefill
            seen = 0
            for t, n in r["events"]:
                for j in range(seen, seen + n):
                    if j > 0:
                        token_events.append((t, len(r["prompt"]) + j))
                seen += n
        self.moved = {k: after[k] - before[k] for k in after}

        def ms(q):
            return 1e3 * percentile(ttft, q) if ttft else None
        return dict(
            # the rate is the cell's end-to-end metric; the first-token
            # times stand beside it on the run's `window_metrics` line
            metrics={"decode_tokens_per_s": tokens / (t1 - t0),
                     "ttft_p50_ms": ms(0.5), "ttft_p90_ms": ms(0.9),
                     "ttft_p95_ms": ms(0.95),
                     "ttft_mean_ms": 1e3 * sum(ttft) / len(ttft)
                     if ttft else None},
            attempted=len(sent),
            failed=len(sent) - len(self.sent_ok), elapsed_s=t1 - t0,
            samples=dict(
                ttft=len(ttft), itl_gaps=len(gaps), tokens=tokens,
                prompts_not_chunked=sum(
                    len(r["prompt"]) <= self.mix["warm"]["plain_prompts"][-1]
                    for r in sent),
                drain_s=time.perf_counter() - t1, counters_moved=self.moved),
            counters=dict(
                tokens=tokens, ttft=ttft, itl_gaps=gaps,
                token_events=token_events,
                slots=self.cell["slots"], t0=t0, t1=t1, kv_stats=self.moved,
                prompt_tokens_sent=sum(len(r["prompt"]) for r in sent)))

    def sample(self):
        """The longest finished request sent in the window and
        ``check_requests - 1`` more drawn from the seed."""
        done = sorted(self.sent_ok, key=lambda r: r["t_send"])
        if not done:
            return []
        longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"])
                      + len(done[i]["tokens"]))
        rest = [i for i in range(len(done)) if i != longest]
        k = min(len(rest), self.mix["check_requests"] - 1)
        picked = np.random.default_rng(self.seed + 1).choice(
            rest, k, replace=False) if k else []
        return [done[longest]] + [done[int(i)] for i in picked]

    def gaps(self, control=None):
        """(mean, widest) gap below the reference's best, over the served
        tokens of the sample. The mean is steady from seed to seed and is
        what a lower precision moves; the widest catches one altered token."""
        gc.collect()    # the engine's pool is freed before the reference runs
        every = np.concatenate([
            self.ref.served_token_gaps(
                self.params, self.config, r["prompt"], r["tokens"],
                self.cell["max_len"], control=control)
            for r in self.sampled])
        return float(every.mean()), float(every.max())

    def check(self):
        ok = [r for r in self.records if r["error"] is None]
        exact = [
            ("failed_requests", len(self.records) - len(ok)),
            ("requests_finished_in_window", 0 if self.sent_ok else 1),
            ("short_or_long_replies",
             sum(len(r["tokens"]) != r["want"] for r in ok)),
            ("streamed_unequal_to_final",
             sum(r["streamed"] != r["tokens"] for r in ok)),
            ("ticks_not_on_the_paged_kernel",
             self.moved["attn_ticks_gather"]),
            ("kernel_ticks_missing",
             0 if self.moved["attn_ticks_kernel"] > 0 else 1),
            ("page_allocations_failed", self.moved["alloc_failures"])]
        self.sampled = self.sample()
        print(json.dumps(dict(
            checked_requests=len(self.sampled),
            checked_served_tokens=sum(len(r["tokens"])
                                      for r in self.sampled))), flush=True)
        limits = self.cell["limits"]
        mean, widest = self.gaps() if self.sent_ok else (None, None)
        return ([dict(name="served_token_gap_mean", value=mean,
                      limit=limits["served_token_gap_mean"]),
                 dict(name="served_token_gap_max", value=widest,
                      limit=limits["served_token_gap_max"])]
                + [dict(name=k, value=v, limit=0) for k, v in exact])

    def control(self):
        """After ``check``: the same sample, the control in the program's
        place."""
        mean, widest = self.gaps(control=self.config["control_dtype"])
        return {"served_token_gap_mean": mean, "served_token_gap_max": widest}

    def close(self):
        self.closing.set()
        if self.engine is not None:
            self.engine.stop()
            self.engine = None
        self.params = None
