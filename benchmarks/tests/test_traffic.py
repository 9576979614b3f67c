"""The traffic generator: the same seed gives the same requests, every seed
carries the same work, and a generation mix warms every prefill shape its own
lengths can reach."""

import numpy as np
import pytest

from benchmarks import run, traffic

MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")
GENERATE = [w["name"] for w in MANIFEST["workloads"]
            if run.load_json(run.HERE, "workloads",
                             f"{w['name']}.json")["driver"] == "generate"]


def lengths(plans):
    return sorted((len(p), o) for plan in plans for p, o in plan)


@pytest.mark.parametrize("workload", GENERATE)
def test_seed_permutes_the_work_and_repeats_itself(workload):
    _, cell, config = run.find_cell(MANIFEST, workload)
    mix = traffic.load(cell["traffic"])
    a, b, c = (traffic.closed_loop_requests(mix, s, config["vocab_size"])
               for s in (3, 3, 2147483999))
    assert len(a) == mix["clients"] == cell["slots"]
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for pa, pb in zip(a, b) for x, y in zip(pa, pb))
    assert lengths(a) == lengths(c)
    assert [len(p) for p, _ in a[0]] != [len(p) for p, _ in c[0]]
    p = mix["prompt"]
    assert all(p["min"] <= n <= p["max"] and n + o <= mix["max_total"]
               <= cell["max_len"] for n, o in lengths(a))


@pytest.mark.parametrize("workload", GENERATE)
def test_mix_warms_every_prefill_shape_its_lengths_reach(workload):
    """The engine pads a prompt of up to ``prefill_chunk`` (256) tokens to
    the next power of two and prefills same-padded arrivals of one tick as a
    group padded to a power of two of rows; a longer prompt goes in windows
    of the chunk and a last one padded likewise."""
    _, cell, config = run.find_cell(MANIFEST, workload)
    mix = traffic.load(cell["traffic"])
    chunk = cell.get("engine", {}).get("prefill_chunk", 256)

    def padded(n):
        return max(8, 1 << (n - 1).bit_length())

    def last_window(n):
        return padded(n % chunk or chunk)
    sent = [n for n, _ in lengths(traffic.closed_loop_requests(
        mix, 5, config["vocab_size"]))]
    warm = mix["warm"]
    assert {padded(n) for n in sent if n <= chunk} \
        == {padded(n) for n in warm["plain_prompts"]}
    assert all(n <= chunk for n in warm["plain_prompts"])
    assert {last_window(n) for n in sent if n > chunk} \
        <= {last_window(n) for n in warm["chunked_prompts"]}
    assert all(n > chunk for n in warm["chunked_prompts"])
    # powers of two up to half the slots: more than half the callers
    # arriving with one padded length inside one tick is what a closed loop
    # of staggered callers does not do (and a compile in the window would
    # make that run not correct)
    assert warm["group_sizes"] == [k for k in (1, 2, 4, 8, 16)
                                   if 2 * k <= cell["slots"]]


def test_image_frame_repeats_its_distinct_images():
    params = dict(rows_per_pass=10, distinct_images=4)
    images, col = traffic.image_frames(params, 7, 8, 3)
    again, _ = traffic.image_frames(params, 7, 8, 3)
    assert col.shape == (10, 8, 8, 3) and col.dtype == np.uint8
    assert np.array_equal(images, again)
    assert np.array_equal(col[5], images[1])
    assert not np.array_equal(images, traffic.image_frames(params, 8, 8, 3)[0])
