"""Questions about registered documents behind ``GenerationEngine``'s HTTP
endpoint, closed loop: ``drivers/generate.py``'s callers, window accounting
and check, with every request ``document + question`` under the document's
``prefix_key`` and ``prefix_len``. Each caller owns one document. The
documents are registered in set-up through the same path (one request each: a
miss, which prefills the document in chunks and stores its pages and its
state snapshot); in the window every admission is a prefix hit.

The traffic file's ``documents`` fixes the lengths; the seed decides which
caller holds which length, the documents' tokens, and (through
``traffic.closed_loop_requests``, whose prompts are the questions here) the
order of each caller's questions: every seed carries the same work.
"""

import http.client
import json
import math
import threading
import time

import numpy as np

from benchmarks import traffic
from benchmarks.drivers import generate as base
# at import, so that a program without the hybrid block stops here, before
# any weight is made
from mmlspark_tpu.models.zoo.transformer import (SparseAttention,
                                                 TransformerConfig)

KIND = {"lightning-attn": "lightning", "minicpm4": "sparse"}


def program_config(config, max_len):
    """The repo's ``TransformerConfig`` for a MiniCPM-SALA ``config.json``:
    every number is the file's, under the program's names."""
    import jax.numpy as jnp
    if (config["lightning_nh"], config["lightning_head_dim"]) != (
            config["num_attention_heads"], config["head_dim"]):
        raise ValueError("the program gives both mixers one head count "
                         "and one head size")
    return TransformerConfig(
        vocab=config["vocab_size"], layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_len=max_len, causal=True,
        dtype=jnp.dtype(config["compute_dtype"]), norm="rmsnorm",
        position="rope", rope_theta=float(config["rope_theta"]),
        mixers=tuple(KIND[m] for m in config["mixer_types"]),
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        sparse=SparseAttention(**config["sparse_config"]),
        embed_scale=float(config["scale_emb"]),
        residual_scale=config["scale_depth"] / math.sqrt(
            config["published"]["num_hidden_layers"]),
        logit_scale=config["dim_model_base"] / config["hidden_size"])


class Client(base.Client):
    """``generate.Client`` whose plan entries are ``(document key, document
    length, prompt, output length)``. Its ``one`` is the parent's with the
    two prefix fields in the body (the parent builds its body in line; a
    hook there is a benchmark PR's edit)."""

    def run(self):
        time.sleep(self.start_delay)
        i = 0
        while not self.closing.is_set():
            self.records.append(self.one(*self.plan[i % len(self.plan)]))
            i += 1

    def one(self, key, prefix_len, prompt, want):
        rec = dict(prompt=prompt, want=want, events=[], streamed=[],
                   tokens=None, error=None, t_done=None)
        body = json.dumps({"tokens": [int(t) for t in prompt],
                           "max_new": want, "stream": True,
                           "prefix_key": key, "prefix_len": prefix_len})
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        try:
            rec["t_send"] = time.perf_counter()
            conn.request("POST", self.path, body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}"
                return rec
            for line in iter(resp.readline, b""):
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


def documents(mix, seed, vocab):
    """``[(key, tokens)]``, one per caller: the mix's lengths in the seed's
    order, tokens from the seed."""
    rng = np.random.default_rng([seed, 1])
    d = mix["documents"]
    lengths = d["shortest"] + d["step"] * rng.permutation(d["count"])
    return [(f"doc-{i}", rng.integers(1, vocab, int(n)).astype(np.int32))
            for i, n in enumerate(lengths)]


class Driver(base.Driver):
    def __init__(self, cell, config, seed, reference):
        from mmlspark_tpu.serving.generation import GenerationEngine
        self.cell, self.config, self.seed, self.ref = (
            cell, config, seed, reference)
        self.mix = traffic.load(cell["traffic"])
        if self.mix["clients"] != self.mix["documents"]["count"]:
            raise ValueError("one document a caller")
        t0 = time.perf_counter()
        self.params = reference.make_weights(config, seed)
        vocab = config["vocab_size"]
        self.docs = documents(self.mix, seed, vocab)
        self.plan = [
            [(key, len(doc), np.concatenate([doc, question]), want)
             for question, want in cycle]
            for (key, doc), cycle in zip(
                self.docs, traffic.closed_loop_requests(self.mix, seed,
                                                        vocab))]
        t1 = time.perf_counter()
        self.engine = GenerationEngine(
            self.params, program_config(config, cell["max_len"]),
            max_slots=cell["slots"], max_len=cell["max_len"],
            reply_timeout=600.0, **cell.get("engine", {}))
        self.where = dict(weights_s=t1 - t0,
                          engine_s=time.perf_counter() - t1)
        self.closing = threading.Event()
        self.clients = []
        self.records = []

    def counters(self):
        kv = self.engine.decoder._kv.stats
        stats = self.engine.decoder.stats
        return dict(
            base.Driver.counters(self),
            attn_ticks_sparse=int(kv.get("attn_ticks_sparse", 0)),
            attn_ticks_dense=int(kv.get("attn_ticks_dense", 0)),
            snapshots_stored=int(kv.get("state_snapshots_stored", 0)),
            snapshots_restored=int(kv.get("state_snapshots_restored", 0)),
            snapshots_evicted=int(kv.get("state_snapshots_evicted", 0)),
            prefix_hits=int(stats.get("prefix_hits", 0)),
            prefix_misses=int(kv.get("prefix_misses", 0)))

    def drive(self, requests):
        """Hand the decoder ``(key, prefix length, prompt, output length)``
        requests at once and step it until they are done. Only before the
        engine's own thread exists."""
        decoder = self.engine.decoder
        tickets = [decoder.submit(prompt, want, prefix_key=key,
                                  prefix_len=plen)
                   for key, plen, prompt, want in requests]
        while not all(t.done for t in tickets):
            decoder.step()
        for t in tickets:
            decoder.result(t)           # raises what the request raised

    def warm(self):
        """Register every document (a miss each: the whole document through
        the chunk program, pages and snapshot stored), then one hit a chunk
        width the questions can reach, so that every program of the window
        has run; then the engine's thread and the closed loop for
        ``ramp_seconds``."""
        rng = np.random.default_rng([self.seed, 2])
        vocab, w = self.config["vocab_size"], self.mix["warm"]

        def ask(key, doc, n):
            return (key, len(doc), np.concatenate(
                [doc, rng.integers(1, vocab, n).astype(np.int32)]),
                w["register_output"])
        t0 = time.perf_counter()
        self.drive([ask(key, doc, w["questions"][0])
                    for key, doc in self.docs])
        t1 = time.perf_counter()
        before = self.counters()
        for n in w["questions"]:
            self.drive([ask(*self.docs[0], n)])
        moved = {k: v - before[k] for k, v in self.counters().items()}
        if (moved["prefix_hits"] != len(w["questions"])
                or moved["snapshots_restored"] != moved["prefix_hits"]):
            raise RuntimeError(f"warm-up's hits did not restore: {moved}")
        self.where.update(register_s=t1 - t0,
                          warm_hits_s=time.perf_counter() - t1)
        print(json.dumps(dict(setup_where=self.where)), flush=True)
        self.engine.start()
        self.clients = [
            Client(self.engine.address, plan, self.closing,
                   i * self.mix["start_stagger_s"])
            for i, plan in enumerate(self.plan)]
        for c in self.clients:
            c.start()
        time.sleep(self.mix["ramp_seconds"])

    def check(self):
        t0 = time.perf_counter()
        compared = base.Driver.check(self)
        print(json.dumps(dict(reference_s=time.perf_counter() - t0)),
              flush=True)
        m = self.moved
        exact = [
            ("prefix_misses_in_window", m["prefix_misses"]),
            ("ticks_of_a_sparse_layer_off_the_sparse_path",
             m["attn_ticks_dense"]),
            ("sparse_ticks_missing", 0 if m["attn_ticks_sparse"] > 0 else 1),
            ("admissions_without_a_restored_snapshot",
             m["prefix_hits"] - m["snapshots_restored"])]
        return compared + [dict(name=k, value=v, limit=0) for k, v in exact]
