"""CPU chaos tests: the acceptance scenarios for the resilience subsystem, run
in-process through the full config-driven app (Main -> component graph -> Gym).

(a) SIGTERM mid-run -> in-flight step finishes -> out-of-schedule checkpoint ->
    warmstart resumes at the right step with losses identical to an
    uninterrupted twin run.
(b) NaN gradients under `skip_step` -> the poisoned step's update is skipped
    (branch-free, inside the jitted program), the budget is decremented, and the
    run finishes with a finite loss.
(c) Corrupted newest checkpoint -> manifest verification fails -> resume
    resolution walks back to the previous verifiable ring folder and the run
    continues from there (satellite of ISSUE 4).
"""

import json
from pathlib import Path

import numpy as np
import pytest

from modalities_tpu.dataloader.packed_data import write_pbin_file
from modalities_tpu.main import Main
from modalities_tpu.resilience import PreemptionShutdown
from modalities_tpu.resilience.events import counts_since, snapshot_counts
from modalities_tpu.resilience.faults import arm_faults
from modalities_tpu.resilience.manifest import MANIFEST_FILE_NAME, resolve_resume_folder

CONFIG = Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu.yaml"
WARMSTART_CONFIG = (
    Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu_warmstart.yaml"
)


@pytest.fixture
def workdir(tmp_path, monkeypatch):
    """Like the e2e fixture, but with enough tokens for the 12-step twin runs
    (12 steps x 64 global batch x 64 seq = 49152 tokens + shuffle slack)."""
    rng = np.random.default_rng(0)
    (tmp_path / "data").mkdir()
    tokens = rng.integers(0, 256, size=56000)
    write_pbin_file(tmp_path / "data" / "lorem_ipsum.pbin", iter([tokens]), token_size_in_bytes=2)
    monkeypatch.chdir(tmp_path)
    return tmp_path


def _write_config(workdir, name, text):
    path = workdir / name
    path.write_text(text)
    return path


def _twelve_step_config(workdir):
    """The base config retargeted to 12 steps, so an uninterrupted run covers the
    same schedule (scheduler total_steps included) as preempt-at-6 + resume-to-12."""
    text = (
        CONFIG.read_text()
        .replace("num_target_tokens: 32768", "num_target_tokens: 49152")
        .replace("num_target_steps: 8", "num_target_steps: 12")
    )
    return _write_config(workdir, "config_12_steps.yaml", text)


def _retargeted_warmstart_config(workdir):
    """The stock warmstart config was written for a dp2 phase 1 (24576 target
    tokens); retarget to 12 steps x 4096 tokens of the dp8 base config."""
    text = WARMSTART_CONFIG.read_text().replace(
        "num_target_tokens: 24576", "num_target_tokens: 49152"
    )
    return _write_config(workdir, "config_warmstart_49152.yaml", text)


def _run(config_path, experiment_id, workdir, resolver=None):
    main = Main(
        config_path,
        experiments_root_path=workdir / "data" / "experiments",
        experiment_id=experiment_id,
        additional_resolver_funs=resolver,
    )
    main.run(main.build_components())
    results = workdir / "data" / "experiments" / experiment_id / "evaluation_results.jsonl"
    return [json.loads(line) for line in results.read_text().splitlines()]


def _train_lines(lines):
    return [r for r in lines if r["dataloader_tag"] == "train"]


def _warmstart(workdir, experiment_id, resume_folder):
    lines = _run(
        _retargeted_warmstart_config(workdir),
        experiment_id,
        workdir,
        resolver={"warmstart_env": lambda key: str(resume_folder)},
    )
    return _train_lines(lines)


# ----------------------------------------------------------- (a) preemption


@pytest.mark.slow  # ~37 s; sealed-checkpoint + resume equivalence stays pinned in tier-1
# by the 2p7b recipe twin and the nan-policy chaos tests; full sigterm loop runs in slow tier
def test_sigterm_forces_checkpoint_and_warmstart_matches_uninterrupted_run(workdir):
    config = _twelve_step_config(workdir)

    # uninterrupted twin: 12 steps under the exact schedule the resumed run sees
    ref = _train_lines(_run(config, "ref", workdir))
    assert ref[-1]["num_train_steps_done"] == 12
    ref_by_step = {r["num_train_steps_done"]: r for r in ref}

    # chaos run: the Trainer SIGTERMs its own process after completing step 6
    arm_faults("sigterm_at_step@6")
    snapshot = snapshot_counts()
    main = Main(
        config,
        experiments_root_path=workdir / "data" / "experiments",
        experiment_id="preempted",
    )
    with pytest.raises(PreemptionShutdown, match="step 6"):
        main.run(main.build_components())

    events = counts_since(snapshot)
    assert events.get("preempt") == 2  # shutdown_requested + checkpoint_saved
    assert events.get("fault") == 1

    # the in-flight step finished and an OUT-OF-SCHEDULE checkpoint (6 is not a
    # multiple of the interval 4) was forced, sealed with a manifest, and made
    # the resume pointer target
    ring = workdir / "data" / "checkpoints"
    forced = [p for p in ring.glob("eid_preempted-*") if "seen_steps_6-" in p.name]
    assert len(forced) == 1
    assert (forced[0] / MANIFEST_FILE_NAME).is_file()
    resume_folder = resolve_resume_folder(ring / "last_checkpoint_info.json")
    assert resume_folder == forced[0]

    # warmstart resumes at step 6 and every overlapping logged interval matches
    # the uninterrupted twin (same params, same sampler position, same schedule)
    resumed = _warmstart(workdir, "resumed", resume_folder)
    assert resumed[0]["num_train_steps_done"] == 8
    assert resumed[-1]["num_train_steps_done"] == 12
    for line in resumed:
        twin = ref_by_step[line["num_train_steps_done"]]
        assert line["metrics"]["consumed tokens"] == twin["metrics"]["consumed tokens"]
        np.testing.assert_allclose(
            line["losses"]["train loss avg"], twin["losses"]["train loss avg"], rtol=1e-5
        )
        np.testing.assert_allclose(
            line["losses"]["train loss last"], twin["losses"]["train loss last"], rtol=1e-5
        )


# -------------------------------------------------------- (b) skip_step


@pytest.mark.slow  # ~15 s subprocess; skip_step budget/window/event semantics
# stay pinned fast by tests/resilience/test_anomaly_tracker.py
# (test_skip_policy_counts_against_budget_and_emits_events) and the raise path
# by test_trainer_raises_on_nonfinite_grads
def test_nan_grads_skip_step_finishes_with_finite_loss(workdir):
    config_text = CONFIG.read_text().replace("anomaly_policy: raise", "anomaly_policy: skip_step")
    config = _write_config(workdir, "config_skip_step.yaml", config_text)

    # poison the gradients at optimizer step 2 (0-based in the jitted program,
    # i.e. the third step, step_id 3)
    arm_faults("nan_grads@2")
    snapshot = snapshot_counts()
    train = _train_lines(_run(config, "skipped", workdir))

    # the run survived to the target with finite losses
    assert train[-1]["num_train_steps_done"] == 8
    assert all(np.isfinite(r["losses"]["train loss avg"]) for r in train)
    assert counts_since(snapshot).get("anomaly") == 1

    # the sink carries the anomaly event with its budget accounting
    sink = workdir / "data" / "experiments" / "skipped" / "telemetry" / "telemetry_rank_0.jsonl"
    events = [json.loads(line) for line in sink.read_text().splitlines()]
    skipped = [e for e in events if e.get("name") == "anomaly/skipped"]
    assert len(skipped) == 1
    assert skipped[0]["step"] == 3
    assert skipped[0]["in_window"] == 1 and skipped[0]["budget"] == 2


# back in tier 1 since PR 47 (10 s under the suite's compile rule): the one end-to-end run under the default policy
def test_nan_grads_default_raise_policy_is_legacy_identical(workdir):
    """Under the default policy the same poison must still kill the run with the
    exact legacy message — resilience armed != behavior changed. The legacy
    guard is the clipper's error_if_nonfinite flag (off in the stock config)."""
    config_text = CONFIG.read_text().replace(
        "norm_type: p2_norm", "norm_type: p2_norm\n    error_if_nonfinite: true"
    )
    config = _write_config(workdir, "config_error_if_nonfinite.yaml", config_text)
    arm_faults("nan_grads@2")
    main = Main(
        config, experiments_root_path=workdir / "data" / "experiments", experiment_id="legacy"
    )
    with pytest.raises(
        RuntimeError,
        match=r"non-finite gradient norm at train step 3 "
        r"\(gradient_clipper\.error_if_nonfinite=True\)",
    ):
        main.run(main.build_components())


# ------------------------------------------- (c) corruption -> ring fallback


@pytest.mark.slow  # ~23 s; corrupt-checkpoint rejection + intact-restore are pinned fast in
# tests/checkpointing/test_corrupt_checkpoint_rejection.py
def test_corrupt_newest_checkpoint_falls_back_and_resumes(workdir):
    # 8 steps -> ring holds verified checkpoints at steps 4 and 8
    base = _train_lines(_run(CONFIG, "base", workdir))
    assert base[-1]["num_train_steps_done"] == 8
    ring = workdir / "data" / "checkpoints"
    newest = next(p for p in ring.glob("eid_base-*") if "seen_steps_8-" in p.name)

    # truncate the biggest committed file in the newest folder
    victim = max(
        (p for p in newest.rglob("*") if p.is_file() and p.name != MANIFEST_FILE_NAME),
        key=lambda p: p.stat().st_size,
    )
    victim.write_bytes(victim.read_bytes()[: victim.stat().st_size // 2])

    # resume resolution refuses the pointer target and walks back to step 4
    snapshot = snapshot_counts()
    resume_folder = resolve_resume_folder(ring / "last_checkpoint_info.json")
    assert "seen_steps_4-" in resume_folder.name
    assert counts_since(snapshot).get("rollback") == 2  # pointer corrupt + fallback pick

    # the resumed run starts where the SURVIVING checkpoint left off: sampler
    # position and token accounting line up with step 4, and it trains to target
    resumed = _warmstart(workdir, "resumed", resume_folder)
    assert resumed[0]["num_train_steps_done"] == 6
    assert resumed[-1]["num_train_steps_done"] == 12
    for line in resumed:
        assert line["metrics"]["consumed tokens"] == line["num_train_steps_done"] * 4096
    assert all(np.isfinite(r["losses"]["train loss avg"]) for r in resumed)
