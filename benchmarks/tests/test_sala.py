"""The hybrid cell (``sala_docqa_closed8``) at sizes a CPU can hold: the
driver registers its documents, every admission of the window is a prefix
hit with a restored snapshot, a sound run is correct and the control is not;
and the hybrid kernels' byte counts against counts made by hand."""

import pytest

from benchmarks import costs_hybrid, run

from . import tiny

CELL = "sala_docqa_closed8"
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6,
              window_size=16, init_blocks=1, dense_len=64)

tiny.SHRINK["generate_docs"] = dict(
    config=dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, lightning_nh=4,
                lightning_nkv=4, lightning_head_dim=16, vocab_size=256,
                dim_model_base=16, num_hidden_layers=4,
                mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                             "minicpm4"],
                sparse_config=SPARSE, compute_dtype="float32",
                param_dtype="float32"),
    cell=dict(slots=4, max_len=256, trace_seconds=1,
              engine={"page_size": 8, "prefill_chunk": 32}),
    mix=dict(clients=4, requests_per_client=4,
             prompt={"median": 12, "sigma": 0.6, "min": 4, "max": 40},
             output={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
             max_total=56, documents={"count": 4, "shortest": 96, "step": 32},
             ramp_seconds=1, check_requests=2,
             warm=dict(plain_prompts=[0], questions=[8, 16, 33],
                       register_output=2)))


@pytest.fixture(scope="module")
def sound():
    return tiny.run_cell(CELL, seed=2147483999, seconds=2.0)


def test_sound_run_is_correct_and_every_admission_restored(sound):
    line, before = sound
    compared = {c["name"]: c for ln in before if "compared" in ln
                for c in ln["compared"]}
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in ("prefix_misses_in_window",
                 "ticks_of_a_sparse_layer_off_the_sparse_path",
                 "admissions_without_a_restored_snapshot",
                 "sparse_ticks_missing", "served_token_gap_mean"):
        assert name in compared
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0


def test_same_seed_same_documents_and_every_seed_the_same_work():
    docs = run.load_by_path("drivers", "generate_docs").documents
    mix = dict(documents=dict(count=8, shortest=12288, step=2560))
    a, b, c = (docs(mix, s, 1000) for s in (5, 5, 6))
    assert all((x[1] == y[1]).all() for x, y in zip(a, b))
    assert sorted(len(t) for _, t in a) == sorted(len(t) for _, t in c) \
        == [12288 + 2560 * i for i in range(8)]
    assert [len(t) for _, t in a] != [len(t) for _, t in c]


def test_control_reads_three_times_the_sound_run():
    with tiny.shrunk():
        manifest = run.load_json(run.ROOT, "BENCHMARK.json")
        _, cell, config = run.find_cell(manifest, CELL)
        reference = run.load_by_path("references", config["reference"])
        driver = run.load_by_path("drivers", cell["driver"]).Driver(
            cell, config, 4, reference)
        try:
            driver.warm()
            driver.window(2.0)
            sound = {c["name"]: c["value"] for c in driver.check()}
            control = driver.control()
        finally:
            driver.close()
    assert control["served_token_gap_mean"] \
        >= 3 * sound["served_token_gap_mean"], (sound, control)


def test_lightning_state_bytes_by_hand():
    # 3 slot-steps, 6 layers, 32 heads of 128: read and write 64 KiB a head
    assert costs_hybrid.lightning_state_bytes(3, 6, 32, 128) \
        == 3 * 6 * 2 * 32 * 128 * 128 * 4


@pytest.mark.parametrize("context,keys,windows", [
    (40, 40, 0),            # dense: every key, no scorer
    (64, 64, 0),            # dense_len itself is still dense
    (65, 5 * 8 + 1, 31),    # 9 blocks, 6 chosen, the newest holds 1 key
    (200, 5 * 8 + 8, 99),   # the newest block full
])
def test_sparse_selected_and_scanned_by_hand(context, keys, windows):
    assert costs_hybrid.sparse_selected_keys(context, SPARSE) == keys
    assert costs_hybrid.compressed_keys_scanned(context, SPARSE) == windows
    # one layer, 2 KV heads of 16 in bf16: 64 bytes a key a tensor
    assert costs_hybrid.sparse_decode_bytes([context], 1, 2, 16, SPARSE) \
        == 64 * (2 * keys + windows)
