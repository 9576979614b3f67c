"""Batch inference through ``ONNXModel.transform``: one frame staged on the
device in set-up, transformed pass after pass until the window ends, results
back on the host as numpy. The window keeps what ``transform`` returned and
does nothing else; every comparison is made after it has closed."""

import time

import numpy as np

from benchmarks import traffic


class Driver:
    def __init__(self, cell, config, seed, reference):
        from mmlspark_tpu.models.onnx_model import ONNXModel
        from mmlspark_tpu.models.zoo.resnet import (ResNetConfig,
                                                    export_resnet_onnx)
        self.cell, self.config, self.seed, self.ref = (
            cell, config, seed, reference)
        self.mix = traffic.load(cell["traffic"])
        side = config["image_size"]
        self.weights = reference.make_weights(config, seed)
        onnx_bytes = export_resnet_onnx(
            ResNetConfig(config["stage_sizes"], config["num_classes"],
                         config["width"]),
            params=self.weights, input_size=side)
        self.model = ONNXModel(
            onnx_bytes, feed_dict={"input": "image"},
            fetch_dict={"logits": "logits"}, argmax_dict={"pred": "logits"},
            transpose_dict={"input": [0, 3, 1, 2]},
            normalize_dict={"input": {"scale": 1.0 / 255.0,
                                      "mean": list(reference.MEAN),
                                      "std": list(reference.STD)}},
            mini_batch_size=cell["mini_batch_size"],
            compute_dtype=config["compute_dtype"])
        self.images, self.col = traffic.image_frames(
            self.mix, seed, side, config["channels"])
        self.staged = None
        self.kept = []      # (logits, pred) of every pass, as returned

    def warm(self):
        side, ch = self.config["image_size"], self.config["channels"]
        self.model.warm_up(batch_sizes=[self.cell["mini_batch_size"]],
                           input_specs={"input": (np.uint8, (side, side, ch))})
        from mmlspark_tpu.core import DataFrame
        # one counted h2d at ingest; every pass reads the staged column
        self.staged = DataFrame(
            {"image": self.col},
            npartitions=self.mix["partitions"]).device_put(["image"])
        self.one_pass()     # the runner's own first-call work, off the clock
        self.kept.clear()

    def one_pass(self):
        out = self.model.transform(self.staged)
        logits, pred = np.asarray(out["logits"]), np.asarray(out["pred"])
        self.kept.append((logits, pred))
        return len(logits)

    def counters(self):
        from mmlspark_tpu.core.residency import M_HITS, M_MISSES
        from mmlspark_tpu.ops.compile_cache import M_STEADY_RECOMPILES
        return dict(stages=self.model.stage_counters.snapshot(),
                    residency_hits=M_HITS.labels().get(),
                    residency_misses=M_MISSES.labels().get(),
                    steady_recompiles=M_STEADY_RECOMPILES.labels().get())

    def window(self, seconds):
        before = self.counters()
        rows = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            rows += self.one_pass()
        elapsed = time.perf_counter() - t0
        after = self.counters()
        stages = {k: v["seconds"] - before["stages"].get(k, {}).get(
            "seconds", 0.0) for k, v in after["stages"].items()}
        self.moved = {k: after[k] - before[k]
                      for k in ("residency_hits", "residency_misses",
                                "steady_recompiles")}
        return dict(
            metrics={"transform_rows_per_s": rows / elapsed},
            attempted=rows, failed=0, elapsed_s=elapsed,
            counters=dict(stage_seconds=stages, rows=rows,
                          passes=len(self.kept), t0=t0, t1=t0 + elapsed,
                          partitions=self.mix["partitions"],
                          batch=self.cell["mini_batch_size"], **self.moved))

    def sample_rows(self):
        """(rows of a pass drawn from the seed, their images)."""
        rows = np.sort(np.random.default_rng(self.seed + 1).choice(
            self.mix["rows_per_pass"], self.mix["check_rows"], replace=False))
        return rows, self.images[rows % len(self.images)]

    def check(self):
        """Every pass equals the first bit for bit; a seeded sample of the
        last pass's rows against the float32 reference."""
        first = self.kept[0][0]
        unequal = sum(not np.array_equal(logits, first)   # same rows, same
                      for logits, _ in self.kept[1:])     # program
        logits, pred = self.kept[-1]
        logits = np.asarray(logits, np.float32)
        n = len(logits)
        rel = None              # a short pass has no rows to compare
        if n == self.mix["rows_per_pass"]:
            rows, images = self.sample_rows()
            want = self.ref.logits(self.weights, images)
            rel = float(np.linalg.norm(logits[rows] - want)
                        / np.linalg.norm(want))
        exact = [
            ("rows_missing", self.mix["rows_per_pass"] - n),
            ("nonfinite_logits", int((~np.isfinite(logits)).sum())),
            ("pred_not_argmax", int((pred != logits.argmax(axis=1)).sum())),
            ("passes_unequal_to_first", unequal),
            # staged once in set-up, never fed from the host again
            ("columns_staged_in_window", self.moved["residency_misses"]),
            ("steady_recompiles_in_window", self.moved["steady_recompiles"])]
        limits = self.cell["limits"]
        return ([dict(name="logits_rel_l2", value=rel,
                      limit=limits["logits_rel_l2"])]
                + [dict(name=k, value=v, limit=0) for k, v in exact])

    def control(self):
        """The same comparison with the reference in the control precision
        in the program's place: the number a sound limit must reject."""
        _, images = self.sample_rows()
        want = self.ref.logits(self.weights, images)
        low = self.ref.logits(self.weights, images,
                              control=self.config["control_dtype"])
        if not np.isfinite(low).all():
            return {"logits_rel_l2": None}      # the control gave no number
        return {"logits_rel_l2": float(np.linalg.norm(low - want)
                                       / np.linalg.norm(want))}

    def close(self):
        self.staged = None
        self.kept = []
