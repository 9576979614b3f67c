"""chip_smoke.py on the CPU: it refuses to run without a chip, and ``--small``
rehearses every phase at toy sizes in interpret mode.

The rehearsals run in child processes (they inherit ``JAX_PLATFORMS=cpu`` from
conftest's ``os.environ``), once per module; a ``--small`` line is never a
chip result and its device line says ``cpu``.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)
import bench  # noqa: E402
import chip_smoke  # noqa: E402


def _run(args, env_extra, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "MMLSPARK_TPU_PALLAS",
                        "MMLSPARK_TPU_FORCE_PLATFORM")}
    env.update(env_extra)
    r = subprocess.run([sys.executable, SCRIPT, *args], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    return r, lines


@pytest.fixture(scope="module")
def small_run(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax-cache"))
    r, lines = _run(["--small"], {"JAX_COMPILATION_CACHE_DIR": cache})
    assert r.returncode == 0, r.stderr[-3000:]
    return lines, cache


@pytest.fixture(scope="module")
def small_run_4(tmp_path_factory):
    # conftest's XLA_FLAGS give the child 8 virtual CPU devices
    r, lines = _run(["--small", "--chips", "4"], {})
    assert r.returncode == 0, r.stderr[-3000:]
    return lines


def test_refuses_the_cpu_and_names_it():
    r, lines = _run([], {})
    assert r.returncode != 0
    assert lines == []                    # no result line of any kind
    assert "'cpu'" in r.stderr and "TPU" in r.stderr


@pytest.mark.parametrize("var", ["MMLSPARK_TPU_FORCE_PLATFORM",
                                 "MMLSPARK_TPU_PALLAS"])
def test_refuses_a_steered_run(var, monkeypatch, capsys):
    monkeypatch.setenv(var, "tpu" if "PLATFORM" in var else "1")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert var in capsys.readouterr().err


def test_small_runs_every_phase_in_interpret_mode(small_run):
    lines, _ = small_run
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert sorted(phases) == ["A.transform", "B.decode", "C.train",
                              "F.hybrid", "G.routed", "H.conv_gqa",
                              "I.latent", "J.state_space"]
    for ln in phases.values():
        assert ln["ok"] is True and ln["failed"] == []
        assert ln["small"] is True and ln["platform"] == "cpu"
        assert ln["native_available"] is True
    assert phases["A.transform"]["steady_state_recompiles"] == 0
    assert phases["B.decode"]["paged_attn"] == "kernel"
    assert phases["B.decode"]["kernel_compiled"] is False   # interpreted
    assert phases["B.decode"]["attn_ticks"]["gather"] == 0
    assert 0 < phases["B.decode"]["int8_quant_error_last"] < 0.05
    # F: a registered prefix past dense_len, then a restored hit whose
    # tokens the plain float32 reference puts first
    assert phases["F.hybrid"]["attn_ticks_sparse"] > 0
    assert phases["F.hybrid"]["gap_max"] <= chip_smoke.TIE_TOL
    # G: every tick on the three routed-decoder kernels, nothing dropped
    assert phases["G.routed"]["attn_ticks_kda"] > 0
    assert phases["G.routed"]["moe"]["pairs_dropped"] == 0
    assert phases["G.routed"]["gap_mean"] <= chip_smoke.ROUTED_GAP_MEAN
    assert phases["H.conv_gqa"]["attn_ticks_gqa"] > 0
    assert phases["H.conv_gqa"]["moe"]["pairs_held"] \
        == phases["H.conv_gqa"]["moe"]["pairs_routed"] > 0
    assert phases["H.conv_gqa"]["gap_mean"] <= chip_smoke.CONV_GQA_GAP_MEAN
    # the experts' product alone: a tile a step on a tick's layout, a run of
    # tiles a step on a carrying step's
    alone = phases["H.conv_gqa"]["product_alone"]
    assert alone["tick"]["steps"] == alone["tick"]["tiles"]
    assert alone["carrying"]["steps"] < alone["carrying"]["tiles"]
    assert alone["carrying"]["err"] < chip_smoke.EXPERTS_ALONE_TOL
    latent = phases["I.latent"]
    assert latent["attn_ticks_latent"] > 0
    assert latent["prefix_tokens_shared"] == 2 * latent["context"]
    assert latent["moe"]["pairs_held"] == latent["moe"]["pairs_routed"] > 0
    assert latent["gap_mean"] <= chip_smoke.LATENT_GAP_MEAN
    # J: every tick on the state-space step and the grouped-query kernel,
    # the step alone one step of the recurrence
    ssm = phases["J.state_space"]
    assert ssm["attn_ticks_ssm"] == ssm["attn_ticks_gqa"] > 0
    assert ssm["ssm_state_rows"] > 0 == ssm["moe"]["pairs_dropped"]
    assert 0 < ssm["moe"]["pairs_held"] < ssm["moe"]["pairs_routed"]
    assert ssm["gap_mean"] <= chip_smoke.SSM_GAP_MEAN
    assert ssm["step_alone"]["y_err"] < 1e-4
    assert phases["C.train"]["pallas_histogram_traces"] > 0
    assert (phases["C.train"]["pallas_interpreted"]
            == phases["C.train"]["pallas_histogram_traces"])


def test_last_line_is_the_device_line_and_nothing_more(small_run):
    lines, _ = small_run
    last = lines[-1]
    assert sorted(last) == ["device", "ok"]
    assert last["ok"] is True
    assert sorted(last["device"]) == ["count", "kind", "platform"]
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1


def test_cache_goes_where_jax_variable_says(small_run):
    lines, cache = small_run
    assert {ln["cache_dir"] for ln in lines if "cache_dir" in ln} == {cache}
    assert os.listdir(cache), "nothing was cached where the variable points"


def test_cache_defaults_to_one_fixed_path_in_the_checkout():
    r, lines = _run(["--small", "--phase", "A"], {})
    assert r.returncode == 0, r.stderr[-3000:]
    assert lines[0]["phase"] == "A.transform"
    assert lines[0]["cache_dir"] == os.path.join(REPO, ".jax_cache")


def test_chips_4_runs_only_the_cross_chip_paths(small_run_4):
    phases = {ln["phase"]: ln for ln in small_run_4 if "phase" in ln}
    assert sorted(phases) == ["D.gbdt_data_parallel", "E.decode_mesh"]
    gbdt, mesh = phases["D.gbdt_data_parallel"], phases["E.decode_mesh"]
    assert gbdt["ok"] and mesh["ok"]
    assert gbdt["collectives"].get("all-reduce", 0) > 0
    assert abs(gbdt["auc_4_devices"] - gbdt["auc_1_device"]) <= 0.002
    assert mesh["mesh"] == "dp2xtp2" and mesh["paged_attn"] == "kernel"
    assert mesh["collectives"].get("all-reduce", 0) > 0
    assert len(gbdt["hbm_peak_bytes"]) == 4   # one reading per device
    assert small_run_4[-1]["device"]["count"] >= 4


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="peak table"):
        bench.peak_flops("Mystery Accelerator 9000")
