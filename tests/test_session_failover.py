"""Session survivability: durable session journaling (insert records,
append-only emitted-token tails, torn-tail repair, compaction), KV-page
export/adopt bitwise parity across pools (bf16 and the int8/fp8
quantized modes, scale pools included), cold-path re-prefill and
warm-path page adoption on ``ContinuousDecoder`` — both token-identical
to the uninterrupted run, the warm path with ZERO re-prefilled tokens —
and the cluster-level failover drill: a 3-worker ``ServingCluster``
where one worker is killed mid-decode (journal-replay reassignment over
``/_adopt``) and one is gracefully drained (exported page blobs ride
the same hop), with ``sessions_lost == 0``.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.models.zoo.transformer import (TransformerConfig,
                                                 init_transformer)
from mmlspark_tpu.serving.continuous import ContinuousDecoder
from mmlspark_tpu.serving.journal import ServingJournal
from mmlspark_tpu.serving.kv_pool import PagedKVPool

CFG = TransformerConfig(vocab=128, layers=2, d_model=64, heads=4, d_ff=128,
                        max_len=64, causal=True, norm="rmsnorm",
                        position="rope", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_transformer(CFG, seed=0)


# ---------------------------------------------------------------------------
# durable session records in the journal


class TestJournalSessions:
    def test_session_round_trip(self, tmp_path):
        path = str(tmp_path / "w.journal")
        j = ServingJournal(path, fsync=False)
        j.record_session("s1", [5, 6, 7], {"max_new": 8, "temperature": 0.0,
                                           "seed": 3}, phash="abc")
        j.record_session_tokens("s1", [10])
        j.record_session_tokens("s1", [11, 12])
        j.record_session("s2", [1], {"max_new": 4})
        j.record_session_end("s2")
        j.close()
        got = ServingJournal.scan_sessions(path)
        # s2 completed (sess_end) so only s1 is live
        assert set(got) == {"s1"}
        assert got["s1"]["prompt"] == [5, 6, 7]
        assert got["s1"]["params"]["max_new"] == 8
        assert got["s1"]["phash"] == "abc"
        assert got["s1"]["emitted"] == [10, 11, 12]

    def test_torn_tail_keeps_prefix(self, tmp_path):
        """A crash mid-append leaves a half-written last line; every record
        before it must still scan."""
        path = str(tmp_path / "w.journal")
        j = ServingJournal(path, fsync=False)
        j.record_session("s1", [2], {"max_new": 6})
        j.record_session_tokens("s1", [20, 21])
        j.close()
        with open(path, "a") as fh:
            fh.write('{"t": "tail", "sid": "s1", "toks": [99')  # torn
        got = ServingJournal.scan_sessions(path)
        assert got["s1"]["emitted"] == [20, 21]
        # reopening repairs the tear so later appends stay parseable
        j2 = ServingJournal(path, fsync=False)
        j2.record_session_tokens("s1", [22])
        j2.close()
        assert ServingJournal.scan_sessions(path)["s1"]["emitted"] == \
            [20, 21, 22]

    def test_compaction_merges_tails(self, tmp_path):
        path = str(tmp_path / "w.journal")
        j = ServingJournal(path, fsync=False)
        j.record_session("s1", [3], {"max_new": 600})
        for k in range(400):
            j.record_session_tokens("s1", [k])
        assert j.maybe_compact(epoch=0, min_lines=64)
        # one sess + one merged tail, nothing lost
        with open(path) as fh:
            recs = [json.loads(line) for line in fh if line.strip()]
        kinds = [r["t"] for r in recs if r["t"] in ("sess", "tail")]
        assert kinds == ["sess", "tail"]
        j.close()
        assert ServingJournal.scan_sessions(path)["s1"]["emitted"] == \
            list(range(400))

    def test_replay_sessions_counts_metric(self, tmp_path):
        path = str(tmp_path / "w.journal")
        j = ServingJournal(path, fsync=False)
        j.record_session("s1", [4], {"max_new": 2})
        j.record_session_tokens("s1", [7])
        j.close()
        j2 = ServingJournal(path, fsync=False)
        live = j2.replay_sessions()
        assert live["s1"]["emitted"] == [7]
        d = j2.digest()
        assert d["live_sessions"] == 1 and not d["closed"]
        j2.close()
        assert j2.closed


# ---------------------------------------------------------------------------
# KV-page export / adopt


class TestPageExportAdopt:
    @pytest.mark.parametrize("kv_dtype", [None, "int8", "fp8"])
    def test_blob_round_trip_is_bitwise(self, kv_dtype):
        src = PagedKVPool(CFG, num_pages=8, page_size=4, kv_dtype=kv_dtype,
                          residency=False)
        dst = PagedKVPool(CFG, num_pages=8, page_size=4, kv_dtype=kv_dtype,
                          residency=False)
        pages = src.alloc(3)
        rng = np.random.default_rng(0)
        # scribble recognizable content into the source pages (values AND
        # scale pools when quantized)
        new = []
        for c in src.buffers:
            nc = {}
            for key, buf in c.items():
                fill = rng.standard_normal(
                    (len(pages),) + buf.shape[1:]).astype(np.float32)
                nc[key] = buf.at[jnp.asarray(pages)].set(
                    jnp.asarray(fill, buf.dtype))
            new.append(nc)
        src.buffers = new
        blob = src.export_session(pages, length=10)
        assert blob["length"] == 10 and blob["n_pages"] == 3
        assert blob["kv_dtype"] == src.kv_dtype
        got = dst.adopt_session(blob)
        assert len(got) == 3
        for sc, dc in zip(src.buffers, dst.buffers):
            for key in sc:
                a = np.asarray(sc[key][jnp.asarray(pages)])
                b = np.asarray(dc[key][jnp.asarray(got)])
                assert a.tobytes() == b.tobytes(), key
        assert src.stats["sessions_exported"] == 1
        assert dst.stats["sessions_adopted"] == 1

    def test_adopt_rejects_layout_mismatch(self):
        src = PagedKVPool(CFG, num_pages=4, page_size=4, residency=False)
        dst = PagedKVPool(CFG, num_pages=4, page_size=8, residency=False)
        blob = src.export_session(src.alloc(1), length=2)
        with pytest.raises(ValueError, match="layout mismatch"):
            dst.adopt_session(blob)

    def test_adopt_quant_mode_must_agree(self):
        src = PagedKVPool(CFG, num_pages=4, page_size=4, kv_dtype="int8",
                          residency=False)
        dst = PagedKVPool(CFG, num_pages=4, page_size=4, residency=False)
        blob = src.export_session(src.alloc(2), length=5)
        with pytest.raises(ValueError, match="layout mismatch"):
            dst.adopt_session(blob)


# ---------------------------------------------------------------------------
# decoder-level failover: cold re-prefill and warm page adoption


def _finish(eng, req, max_steps=400):
    for _ in range(max_steps):
        if req.done:
            break
        eng.step()
    assert req.done
    return eng.session_result(req)


class TestDecoderFailover:
    def _baseline(self, params, prompt, max_new):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        return _finish(eng, eng.submit(prompt, max_new))

    def test_cold_restore_matches_uninterrupted(self, params, tmp_path):
        """Kill mid-decode: the survivor re-prefills from the journal alone
        and the full session is token-identical to the uninterrupted
        run (greedy teacher-forcing)."""
        prompt = np.arange(5, 12, dtype=np.int32)
        want = self._baseline(params, prompt, 12)
        jpath = str(tmp_path / "a.journal")
        ja = ServingJournal(jpath, fsync=False)
        ea = ContinuousDecoder(params, CFG, max_slots=2, max_len=64,
                               journal=ja)
        ra = ea.submit(prompt, 12, session_id="sess-X")
        for _ in range(5):
            ea.step()
        assert ra.tokens and not ra.done   # genuinely mid-decode
        ja.close()                         # SIGKILL: journal is all that's left
        sessions = ServingJournal.scan_sessions(jpath)
        sess = dict(sessions["sess-X"], id="sess-X")
        assert sess["emitted"] == ra.tokens[:len(sess["emitted"])]
        eb = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        rb = eb.restore_session(sess)
        assert rb.pre_emitted == sess["emitted"]
        assert _finish(eb, rb) == want

    def test_warm_adopt_zero_reprefill(self, params, tmp_path):
        """Graceful drain: exported pages adopt into the survivor's pool —
        token-identical AND zero prefills on the adopter."""
        prompt = np.arange(3, 10, dtype=np.int32)
        want = self._baseline(params, prompt, 10)
        ea = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        ra = ea.submit(prompt, 10)
        for _ in range(4):
            ea.step()
        assert ra.tokens and not ra.done
        ckpt = ea.checkpoint_session(ra)
        assert ckpt["kv"] is not None
        assert ckpt["session"]["emitted"] == ra.tokens
        eb = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        rb = eb.restore_session(ckpt["session"], kv_blob=ckpt["kv"])
        assert _finish(eb, rb) == want
        assert eb.stats["prefills"] == 0   # warm: no re-prefilled tokens

    def test_workers_of_one_max_len_derive_one_page(self, params):
        """No ``page_size`` given: two workers of ``max_len`` 1024 both
        serve pages of 64 and hand a session over warm; a worker of
        ``max_len`` 256 serves pages of 16 and refuses the blob with the
        two layouts in its reason."""
        prompt = np.arange(3, 90, dtype=np.int32)     # into a second page
        ea = ContinuousDecoder(params, CFG, max_slots=2, max_len=1024)
        want = _finish(ea, ea.submit(prompt, 10))
        ra = ea.submit(prompt, 10)
        for _ in range(4):
            ea.step()
        ckpt = ea.checkpoint_session(ra)
        assert ckpt["kv"]["page_size"] == 64 and ckpt["kv"]["n_pages"] == 2
        eb = ContinuousDecoder(params, CFG, max_slots=2, max_len=1024)
        rb = eb.restore_session(ckpt["session"], kv_blob=ckpt["kv"])
        assert _finish(eb, rb) == want and eb.stats["prefills"] == 0
        small = ContinuousDecoder(params, CFG, max_slots=2, max_len=256)
        with pytest.raises(ValueError, match=(
                r"layout mismatch: blob \{'page_size': 64.*"
                r"pool \{'page_size': 16")):
            small.restore_session(ckpt["session"], kv_blob=ckpt["kv"])

    def test_double_failover_round_trips(self, params):
        """checkpoint(restore(checkpoint(x))) stays canonical: a second
        hop neither re-forces the prompt nor loses emitted tokens."""
        prompt = np.arange(2, 8, dtype=np.int32)
        want = self._baseline(params, prompt, 12)
        ea = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        ra = ea.submit(prompt, 12)
        for _ in range(4):
            ea.step()
        c1 = ea.checkpoint_session(ra)
        eb = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        rb = eb.restore_session(c1["session"], kv_blob=c1["kv"])
        for _ in range(3):
            eb.step()
        c2 = eb.checkpoint_session(rb)
        # canonical: ORIGINAL prompt and budget, merged emitted tail
        assert c2["session"]["prompt"] == [int(t) for t in prompt]
        assert c2["session"]["params"]["max_new"] == 12
        ec = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        rc = ec.restore_session(c2["session"], kv_blob=c2["kv"])
        assert _finish(ec, rc) == want

    def test_spent_session_restores_completed(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=64)
        req = eng.restore_session({"id": "done", "prompt": [1, 2],
                                   "params": {"max_new": 3},
                                   "emitted": [4, 5, 6]})
        assert req.done and eng.session_result(req) == [4, 5, 6]


# ---------------------------------------------------------------------------
# cluster-level orchestration: kill + drain over /_adopt


class TestClusterFailover:
    def test_kill_reassigns_journaled_sessions(self, tmp_path):
        from mmlspark_tpu.serving.distributed import ServingCluster
        cluster = ServingCluster(3, reply_timeout=5.0,
                                 journal_dir=str(tmp_path))
        try:
            w1 = cluster.worker("worker-1")
            w1.server._journal.record_session(
                "sess-A", [1, 2, 3], {"max_new": 8, "temperature": 0.0,
                                      "seed": 0})
            w1.server._journal.record_session_tokens("sess-A", [10, 11])
            out = cluster.reassign_sessions("worker-1")
            assert out and out.get("adopted") == 1
            adopter = cluster.worker(out["worker"])
            assert adopter.worker_id != "worker-1"
            entry = adopter.adopted_sessions[0]
            assert entry["session"]["id"] == "sess-A"
            assert entry["session"]["emitted"] == [10, 11]
            assert entry["kv"] is None     # kill path is cold
            # write-ahead on the adopter: a second failure replays from its
            # own journal
            got = adopter.server._journal.replay_sessions()
            assert got["sess-A"]["emitted"] == [10, 11]
        finally:
            cluster.close()

    def test_restart_rehydrates_sessions_from_journal(self, tmp_path):
        from mmlspark_tpu.serving.distributed import ServingCluster
        cluster = ServingCluster(2, reply_timeout=5.0,
                                 journal_dir=str(tmp_path))
        try:
            w1 = cluster.worker("worker-1")
            w1.server._journal.record_session(
                "sess-R", [7], {"max_new": 5, "temperature": 0.0})
            w1.server._journal.record_session_tokens("sess-R", [70])
            cluster.restart_worker("worker-1")
            # the replacement reopened the same journal and rehydrated the
            # live session for its engine to restore cold
            w1b = cluster.worker("worker-1")
            assert w1b.server.replayed_sessions["sess-R"]["emitted"] == [70]
        finally:
            cluster.close()

    def test_drain_ships_warm_blobs(self, tmp_path):
        from mmlspark_tpu.serving.distributed import ServingCluster
        cluster = ServingCluster(2, reply_timeout=5.0,
                                 journal_dir=str(tmp_path))
        try:
            w0 = cluster.worker("worker-0")
            blob = {"v": 1, "n_pages": 1, "length": 4, "data": []}
            w0.session_exporter = lambda: [{
                "session": {"id": "sess-W", "prompt": [9],
                            "params": {"max_new": 6}, "emitted": [3]},
                "kv": blob}]
            out = cluster.drain_worker("worker-0")
            assert out.get("adopted") == 1 and out.get("mode") == "warm"
            w1 = cluster.worker("worker-1")
            assert w1.adopted_sessions[0]["kv"] == blob
            # the drained worker is gone from the cluster AND the routing
            ids = [w.worker_id for w in cluster.workers]
            assert "worker-0" not in ids
            assert "worker-0" not in cluster.driver.routing_table()
        finally:
            cluster.close()

    def test_liveness_sweeper_evicts_dead_worker(self, tmp_path):
        import time
        from mmlspark_tpu.serving.distributed import ServingCluster
        cluster = ServingCluster(2, reply_timeout=5.0,
                                 liveness_interval=0.15,
                                 heartbeat_interval=0.05,
                                 journal_dir=str(tmp_path))
        try:
            assert "worker-1" in cluster.driver.routing_table()
            # stop worker-1's heartbeats without deregistering — a SIGKILL
            # as the driver sees it
            w1 = cluster.worker("worker-1")
            w1._hb_stop.set()
            w1._hb_thread.join(timeout=2.0)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if "worker-1" not in cluster.driver.routing_table():
                    break
                time.sleep(0.05)
            assert "worker-1" not in cluster.driver.routing_table()
            assert "worker-0" in cluster.driver.routing_table()
        finally:
            cluster.close()

    def test_session_drill_survives_worker_restart(self, tmp_path):
        """The decode-kill drill in miniature: live journal-backed decode
        sessions, one owning worker replaced mid-stream, every session
        finishes with the exact deterministic token stream."""
        import time
        from mmlspark_tpu.loadgen import SessionDrill
        from mmlspark_tpu.serving.distributed import ServingCluster
        cluster = ServingCluster(3, reply_timeout=5.0)
        try:
            drill = SessionDrill(cluster, n_sessions=4,
                                 tokens_per_session=30, tick_s=0.02,
                                 journal_dir=str(tmp_path)).start()
            time.sleep(0.2)
            cluster.restart_worker("worker-1")
            card = drill.finish(timeout=15.0)
            assert card["lost"] == 0
            assert card["recovered"] >= 1
            assert card["recovery_p99_ms"] is not None
        finally:
            cluster.close()
