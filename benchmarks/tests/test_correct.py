"""How ``correct`` is decided, at sizes a CPU can hold: a sound run passes,
the control (the reference in the nearest precision below the
configuration's, in the program's place) reads at least three times the sound
runs' number, and a run whose timed path is broken comes out not correct.

The limits in the cells' files were set from chip readings at the cells' own
sizes (PERF.md gives them); these tests hold the comparison itself."""

import numpy as np
import pytest

from benchmarks import run

from . import tiny

SEEDS = (3, 4, 2147483999)


def load(cell_driver):
    return run.load_by_path("drivers", cell_driver)


class AlteredLogit:
    """The transform driver with one answer altered where it is produced."""

    def __new__(cls, *args):
        base = load("transform").Driver

        class Broken(base):
            def one_pass(self):
                n = base.one_pass(self)
                logits, pred = self.kept[-1]
                logits[::7, 3] += 0.5 * np.abs(logits).max()
                return n
        return Broken(*args)


class AlteredToken:
    """The generate driver whose engine reports every fifth token of a
    request altered, where the decoder notes it."""

    def __new__(cls, *args):
        driver = load("generate").Driver(*args)
        decoder = driver.engine.decoder
        note, vocab = decoder._note_token, driver.config["vocab_size"]

        def altered(req, tok):
            if len(req.tokens) % 5 == 4:
                tok = (int(tok) + 1) % vocab
            return note(req, tok)
        decoder._note_token = altered
        return driver


@pytest.mark.parametrize("workload,number", [
    ("resnet50_transform_resident", "logits_rel_l2"),
    ("gpt2xl_generate_closed", "served_token_gap_max"),
])
def test_sound_run_is_correct_and_prints_each_number_with_its_limit(
        workload, number):
    line, before = tiny.run_cell(workload, seed=SEEDS[0], seconds=1.5)
    compared = next(ln["compared"] for ln in before if "compared" in ln)
    assert {"name", "value", "limit"} <= set(compared[0])
    assert number in [c["name"] for c in compared]
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("workload,broken", [
    ("resnet50_transform_resident", AlteredLogit),
    ("gpt2xl_generate_closed", AlteredToken),
])
def test_broken_timed_path_is_not_correct(workload, broken):
    line, before = tiny.run_cell(workload, seed=SEEDS[1], seconds=1.5,
                                 driver_override=broken)
    assert line["correct"] is False, before


def drive(workload, seed):
    """(the sound run's numbers, the control's) for one seed, in-process."""
    with tiny.shrunk():
        manifest = run.load_json(run.ROOT, "BENCHMARK.json")
        _, cell, config = run.find_cell(manifest, workload)
        reference = run.load_by_path("references", config["reference"])
        driver = load(cell["driver"]).Driver(cell, config, seed, reference)
        try:
            driver.warm()
            driver.window(2.0)
            sound = {c["name"]: c["value"] for c in driver.check()}
            return sound, driver.control()
        finally:
            driver.close()


@pytest.mark.parametrize("workload,number", [
    ("resnet50_transform_resident", "logits_rel_l2"),
    ("gpt2xl_generate_closed", "served_token_gap_mean"),
])
def test_control_reads_three_times_the_sound_runs(workload, number):
    sound, control = zip(*(drive(workload, s) for s in SEEDS))
    largest = max(s[number] for s in sound)
    smallest = min(c[number] for c in control)
    assert smallest is not None and smallest >= 3 * largest, (sound, control)
