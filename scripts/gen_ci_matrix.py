"""Regenerate the CI test matrix in deploy/ci/pipeline.yaml from tests/.

The reference hand-maintains one CI job per test package
(`/root/reference/pipeline.yaml:540-662`) — which rots the moment a suite
is added without touching the pipeline (exactly what round 3 did). Here the
matrix is *generated*: every `tests/test_*.py` is assigned to a job bucket
by name; files matching no bucket land in the catch-all `misc` job, so a
new suite can never silently drop out of CI. `--check` mode (used by
tests/test_deploy.py and the codegen-freshness CI stage) fails when the
committed pipeline differs from the generated one.
"""

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIPELINE = os.path.join(REPO, "deploy", "ci", "pipeline.yaml")

# bucket name -> regex over the test file name (first match wins, in order)
BUCKETS = [
    ("core", r"test_(core|batching|stages_misc|featurize|tokenizer)\.py"),
    ("onnx", r"test_(onnx|onnx_\w+|torch_export)\.py"),
    ("gbdt", r"test_(gbdt\w*|pallas_kernels)\.py"),
    ("vw", r"test_vw\.py"),
    ("models", r"test_(jax_model|zoo_parallel|moe|pipeline_parallel|"
               r"flash_attention|model_artifact)\.py"),
    ("explainers", r"test_explainers\.py"),
    ("io-serving", r"test_(io_http_serving|serving\w*|port_forwarding|"
                   r"parquet_io|distributed_rendezvous)\.py"),
    ("services", r"test_(services\w*|speech_streaming)\.py"),
    ("ml-misc", r"test_(nn_reco_iforest|train_automl|cyber|image_io)\.py"),
    ("fuzzing", r"test_fuzzing\.py"),
    ("quality", r"test_benchmarks\w*\.py"),
    ("interop", r"test_(interop\w*|codegen)\.py"),
    ("native", r"test_(native|utils|checkpoint_profiling)\.py"),
    # test_deploy.py runs in the docs job below (it checks this very file,
    # so it stays out of the generated buckets); test_observability.py,
    # test_tracing.py, test_reliability.py, test_residency.py,
    # test_kv_pool.py, test_paged_attention.py, test_kv_quant.py
    # (the int8/fp8 page quantization data plane), test_tuning.py,
    # test_slo.py, test_watchdog.py, test_cost_ledger.py,
    # test_federation.py, test_registry.py, test_admission.py,
    # test_loadgen.py (the open-loop scenario harness + scorecard plane
    # that scores everything else), test_session_failover.py (durable
    # session journaling, KV-page export/adopt, and the kill/drain
    # failover drill the serving plane's survivability story rests on),
    # and test_timeseries.py (the
    # fixed-memory metric-history store + alert engine) run in
    # the static-analysis stage — telemetry, the retry/breaker/fault
    # toolkit, the device-residency transfer accounting, the paged-KV
    # pool the serving engine decodes through, the Pallas paged-attention
    # kernel it attends with, the autotuner that picks the data-plane
    # config, the SLO scorecard engine, the device-stall watchdog, the
    # cost-attribution ledger, the cluster metrics federation, and the
    # multi-model traffic plane (registry rollout + weighted-fair
    # admission) are infrastructure the whole matrix depends on, so they
    # gate early, next to the lint that polices them;
    # test_lock_sanitizer.py also runs there (and again, instrumented, in
    # the lock-sanitizer stage below), as do test_tpulint.py — the
    # rule fixtures (TPU017's shard_map-mount check and the TPU019-022
    # sharding rules included) gate next to the lint run itself — and
    # test_collective_audit.py, whose budget semantics the
    # collective-audit stage below enforces; test_mesh_parity.py runs in
    # the dedicated mesh-parity stage below — it needs the
    # 8-simulated-device topology as a hard precondition, not a skip
]

HEADER = """\
# CI matrix (parity role: the reference's pipeline.yaml:540-662 — one job
# per test package, timeouts, retries for flaky suites, coverage upload).
# Written for any YAML-driven runner; jobs shell out to pytest the same way
# the reference shells out to sbt testOnly per package.
#
# The test-matrix jobs are GENERATED by scripts/gen_ci_matrix.py from the
# contents of tests/ — edit the buckets there, then rerun it. A hand edit
# here fails the freshness check in tests/test_deploy.py.
name: mmlspark-tpu-ci
env:
  JAX_PLATFORMS: cpu
  XLA_FLAGS: --xla_force_host_platform_device_count=8
stages:
  - name: style
    script: python -m compileall -q mmlspark_tpu
  - name: static-analysis
    # tpulint runs in baseline-diff mode: the checked-in baseline
    # (tools/tpulint/baseline.json) absorbs accepted findings, so the stage
    # gates on NEW hazards only (docs/static_analysis.md has the workflow).
    # ruff is scoped to pyflakes + import-sort in pyproject.toml; the guard
    # keeps minimal runners (no ruff wheel) green without skipping tpulint.
    script: |
      python scripts/run_tpulint.py mmlspark_tpu scripts tools
      python -m pytest tests/test_tpulint.py tests/test_collective_audit.py tests/test_observability.py tests/test_tracing.py tests/test_reliability.py tests/test_residency.py tests/test_kv_pool.py tests/test_paged_attention.py tests/test_kv_quant.py tests/test_tuning.py tests/test_slo.py tests/test_watchdog.py tests/test_cost_ledger.py tests/test_federation.py tests/test_registry.py tests/test_admission.py tests/test_loadgen.py tests/test_session_failover.py tests/test_timeseries.py tests/test_lock_sanitizer.py -q
      if command -v ruff >/dev/null; then ruff check .; fi
  - name: lock-sanitizer
    # tier-1 concurrency subset re-run with the runtime lock-order
    # sanitizer armed: every Lock/RLock/Condition built through the
    # reliability.lock_sanitizer factories records its acquisition graph,
    # and any dynamic lock-order cycle (or hold past the budget) fails the
    # drill (docs/reliability.md, "deadlock and lock-hold triage").
    script: |
      MMLSPARK_TPU_LOCK_SANITIZER=1 python -m pytest tests/test_lock_sanitizer.py tests/test_serving_distributed.py tests/test_continuous.py -q
  - name: codegen-freshness
    script: |
      python -m mmlspark_tpu.codegen
      python scripts/gen_ci_matrix.py
      git diff --exit-code  # stale generated surface fails the build
  - name: test-matrix
    timeout_minutes: 30
    retries_if_flaky: 2
    jobs:
"""

FOOTER = """\
      - {name: docs,        script: python scripts/doctest_docs.py && python -m pytest tests/test_deploy.py -q}
  - name: mesh-parity
    # kernel-vs-gather decode parity under dp-only / tp-only / dp×tp
    # meshes on the simulated 8-device topology (env above forces it):
    # the shard_map-mounted Pallas kernel must stay token-identical to
    # the gather oracle, pages bitwise, zero steady-state recompiles
    script: python -m pytest tests/test_mesh_parity.py -q
  - name: collective-audit
    # rebuild every audited program (engine ticks, prefill/extend, the
    # zoo's sharded attention + MoE dispatch, tick_core) on the simulated
    # 8-device topology, count collectives in the compiled HLO, and diff
    # against tools/tpulint/collective_budget.json — one compiler upgrade
    # or resharding regression that inserts an extra all-gather fails
    # this stage (docs/performance.md, "Collective budgets")
    script: MMLSPARK_TPU_COLLECTIVE_AUDIT=1 python -m mmlspark_tpu.parallel.collective_audit
  - name: multichip-dryrun
    script: python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
  - name: package
    # build the wheel, install into a clean venv, run the quickstart and a
    # doctest file against the INSTALLED package (the executable slice of
    # the reference's packagePython/testPython, CodegenPlugin.scala:55-67)
    script: bash scripts/test_packaging.sh
  - name: chip-smoke-rehearsal
    # every chip_smoke.py phase at toy sizes on the CPU in interpret mode;
    # bench.py and the full chip_smoke.py need a TPU and exit non-zero here
    script: python chip_smoke.py --small
  - name: scenario-smoke
    # the smoke scenario open-loop against an in-process 3-worker
    # cluster: seeded arrivals, CPU-only, wall-clock-bounded by the
    # scenario duration; --check fails the stage on any lost request, a
    # federated-counter reconciliation miss, or an empty/inconsistent
    # scorecard `timeline` sub-record (docs/observability.md,
    # "Scenario harness and scorecards"); decode-kill then kills a worker
    # under live long-decode sessions and fails the stage unless every
    # session finishes token-identical via journal-replay failover
    # (sessions_lost == 0; docs/reliability.md, "Session recovery and
    # warm handoff")
    timeout_minutes: 5
    script: |
      MMLSPARK_TPU_FEDERATION_INTERVAL=0 python scripts/run_scenarios.py smoke --duration 2 --rate 40 --check
      MMLSPARK_TPU_FEDERATION_INTERVAL=0 python scripts/run_scenarios.py decode-kill --check
"""


def generate() -> str:
    files = sorted(f for f in os.listdir(os.path.join(REPO, "tests"))
                   if f.startswith("test_") and f.endswith(".py")
                   and f not in ("test_deploy.py", "test_observability.py",
                                 "test_tracing.py", "test_reliability.py",
                                 "test_residency.py", "test_kv_pool.py",
                                 "test_paged_attention.py",
                                 "test_kv_quant.py",
                                 "test_tuning.py", "test_slo.py",
                                 "test_watchdog.py", "test_cost_ledger.py",
                                 "test_federation.py",
                                 "test_registry.py", "test_admission.py",
                                 "test_loadgen.py",
                                 "test_session_failover.py",
                                 "test_timeseries.py",
                                 "test_lock_sanitizer.py",
                                 "test_tpulint.py",
                                 "test_collective_audit.py",
                                 "test_mesh_parity.py"))
    groups = {name: [] for name, _ in BUCKETS}
    groups["misc"] = []
    for f in files:
        for name, pat in BUCKETS:
            if re.fullmatch(pat, f):
                groups[name].append(f)
                break
        else:
            groups["misc"].append(f)
    lines = []
    for name in list(dict(BUCKETS)) + ["misc"]:
        if not groups[name]:
            continue
        paths = " ".join(f"tests/{f}" for f in groups[name])
        pad = " " * max(1, 12 - len(name))
        lines.append(f"      - {{name: {name},{pad}"
                     f"script: python -m pytest {paths} -q}}")
    return HEADER + "\n".join(lines) + "\n" + FOOTER


def main() -> int:
    text = generate()
    if "--check" in sys.argv:
        with open(PIPELINE, encoding="utf-8") as fh:
            if fh.read() != text:
                print("deploy/ci/pipeline.yaml is stale — rerun "
                      "scripts/gen_ci_matrix.py", file=sys.stderr)
                return 1
        return 0
    with open(PIPELINE, "w", encoding="utf-8") as fh:
        fh.write(text)
    print(f"wrote {PIPELINE}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
