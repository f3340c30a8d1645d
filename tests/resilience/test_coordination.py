"""Stop-ballot + resume-vote units, and the HLO contract of the consensus
collective: disabled -> the compiled step is byte-identical to a build without
the feature; enabled -> at most ONE extra all-reduce rides the step."""

import json

import jax
import numpy as np
import pytest

from modalities_tpu.resilience.coordination import (
    BALLOT_KEY,
    VOTE_CONTINUE,
    VOTE_ROLLBACK,
    VOTE_STOP,
    agree_resume,
    agree_resume_folder,
    collect_verified_steps,
    make_ballot,
    resolve_consensus,
)
from modalities_tpu.resilience.manifest import atomic_write_json, write_manifest


def test_resolve_consensus_modes():
    assert resolve_consensus("on") is True
    assert resolve_consensus("off") is False
    # auto in a single-process test session: nothing to coordinate
    assert resolve_consensus("auto") is False
    with pytest.raises(ValueError, match="stop_consensus"):
        resolve_consensus("maybe")


def test_vote_ordering_is_severity():
    assert VOTE_CONTINUE < VOTE_STOP < VOTE_ROLLBACK


def test_make_ballot_without_mesh():
    ballot = make_ballot(VOTE_STOP, None)
    assert ballot.shape == (jax.local_device_count(),)
    assert int(np.asarray(ballot).max()) == VOTE_STOP


def test_make_ballot_on_mesh_reduces_with_max():
    from modalities_tpu.running_env.device_mesh import get_device_mesh

    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    ballot = make_ballot(VOTE_ROLLBACK, mesh)
    assert ballot.shape == (8,)
    # the in-step reduction every process reads
    assert int(jax.numpy.max(ballot)) == VOTE_ROLLBACK
    assert BALLOT_KEY == "stop_ballot"


# ------------------------------------------------------------- resume votes


def _seal(ring, step, ok=True):
    folder = ring / (
        f"eid_x-seen_steps_{step}-seen_tokens_{step * 128}-target_steps_12-target_tokens_1536"
    )
    folder.mkdir(parents=True)
    (folder / "blob.bin").write_bytes(b"\x01" * 16)
    write_manifest(folder)
    if not ok:
        (folder / "blob.bin").write_bytes(b"\x02" * 16)  # digest mismatch
    return folder


def _pointer(ring, folder):
    info_path = ring / "last_checkpoint_info.json"
    atomic_write_json(info_path, {"checkpoint_folder_path": str(folder)})
    return info_path


def test_collect_verified_steps_filters_unverifiable(tmp_path):
    ring = tmp_path / "checkpoints"
    ok4 = _seal(ring, 4)
    _seal(ring, 8, ok=False)  # corrupt: must not be offered as a vote
    info_path = _pointer(ring, ok4)
    steps = collect_verified_steps(info_path)
    assert sorted(steps) == [4]
    assert steps[4] == ok4


def test_collect_verified_steps_survives_missing_pointer(tmp_path):
    ring = tmp_path / "checkpoints"
    _seal(ring, 4)
    steps = collect_verified_steps(ring / "last_checkpoint_info.json")
    assert sorted(steps) == [4]


def test_agree_resume_folder_picks_newest_common_step(tmp_path):
    ring = tmp_path / "checkpoints"
    ok4 = _seal(ring, 4)
    ok8 = _seal(ring, 8)
    info_path = _pointer(ring, ok8)
    votes = tmp_path / "votes"
    # host 1 verified only step 4 (its view of step 8 is corrupt/missing)
    votes.mkdir()
    atomic_write_json(
        votes / "resume_vote_a0_h1.json", {"host_id": 1, "attempt": 0, "steps": [4]}
    )
    agreed = agree_resume_folder(
        info_path, votes, host_id=0, host_count=2, attempt=0, deadline_s=5.0,
        sleep_fn=lambda s: None,
    )
    # NOT the local newest (8): the newest step every voter verified
    assert agreed == ok4
    vote_0 = json.loads((votes / "resume_vote_a0_h0.json").read_text())
    assert vote_0["steps"] == [4, 8]


def test_agree_resume_folder_times_out_without_quorum(tmp_path):
    ring = tmp_path / "checkpoints"
    info_path = _pointer(ring, _seal(ring, 4))
    clock_state = [0.0]

    def clock():
        return clock_state[0]

    def sleep(seconds):
        clock_state[0] += seconds

    with pytest.raises(FileNotFoundError, match="quorum"):
        agree_resume_folder(
            info_path, tmp_path / "votes", host_id=0, host_count=2, attempt=0,
            deadline_s=3.0, sleep_fn=sleep, clock=clock,
        )


def test_agree_resume_folder_fails_on_empty_intersection(tmp_path):
    ring = tmp_path / "checkpoints"
    info_path = _pointer(ring, _seal(ring, 8))
    votes = tmp_path / "votes"
    votes.mkdir()
    atomic_write_json(
        votes / "resume_vote_a0_h1.json", {"host_id": 1, "attempt": 0, "steps": [4]}
    )
    with pytest.raises(FileNotFoundError, match="no checkpoint step verifies"):
        agree_resume_folder(
            info_path, votes, host_id=0, host_count=2, attempt=0, deadline_s=5.0,
            sleep_fn=lambda s: None,
        )


def test_agree_resume_folder_quorum_below_host_count(tmp_path):
    """quorum=1: this host may proceed on its own votes (degraded pools)."""
    ring = tmp_path / "checkpoints"
    ok8 = _seal(ring, 8)
    info_path = _pointer(ring, ok8)
    agreed = agree_resume_folder(
        info_path, tmp_path / "votes", host_id=0, host_count=4, attempt=0,
        quorum=1, deadline_s=5.0, sleep_fn=lambda s: None,
    )
    assert agreed == ok8


def test_collect_verified_steps_excludes_burned(tmp_path):
    ring = tmp_path / "checkpoints"
    ok4 = _seal(ring, 4)
    _seal(ring, 8)
    info_path = _pointer(ring, ok4)
    assert sorted(collect_verified_steps(info_path)) == [4, 8]
    assert sorted(collect_verified_steps(info_path, exclude_steps={8})) == [4]


def test_three_disagreeing_rings_agree_on_the_common_step(tmp_path):
    """Three hosts with genuinely different ring views — overlapping but
    unequal step sets — must all derive the same answer: the newest step in the
    full intersection, not any host's local newest."""
    ring = tmp_path / "checkpoints"
    _seal(ring, 4)
    _seal(ring, 8)
    ok12 = _seal(ring, 12)
    info_path = _pointer(ring, ok12)  # this host (0) verified {4, 8, 12}
    votes = tmp_path / "votes"
    votes.mkdir()
    # host 1 lost step 12 to corruption; host 2 only ever synced up to step 8
    atomic_write_json(
        votes / "resume_vote_a0_h1.json", {"host_id": 1, "attempt": 0, "steps": [4, 8]}
    )
    atomic_write_json(
        votes / "resume_vote_a0_h2.json", {"host_id": 2, "attempt": 0, "steps": [8]}
    )
    agreement = agree_resume(
        info_path, votes, host_id=0, host_count=3, attempt=0, deadline_s=5.0,
        sleep_fn=lambda s: None,
    )
    assert agreement.step == 8  # in all three rings; 12 is not
    assert agreement.voters == [0, 1, 2]
    assert not agreement.degraded


def test_disagreeing_rings_with_empty_three_way_intersection_fail(tmp_path):
    """Pairwise overlap is not enough: {12}, {8}, {8,12} share no common step,
    and a resume from ANY of them would leave some host unable to restore."""
    ring = tmp_path / "checkpoints"
    ok12 = _seal(ring, 12)
    info_path = _pointer(ring, ok12)  # host 0 verified only {12}
    votes = tmp_path / "votes"
    votes.mkdir()
    atomic_write_json(
        votes / "resume_vote_a0_h1.json", {"host_id": 1, "attempt": 0, "steps": [8]}
    )
    atomic_write_json(
        votes / "resume_vote_a0_h2.json", {"host_id": 2, "attempt": 0, "steps": [8, 12]}
    )
    with pytest.raises(FileNotFoundError, match="no checkpoint step verifies"):
        agree_resume(
            info_path, votes, host_id=0, host_count=3, attempt=0, deadline_s=5.0,
            sleep_fn=lambda s: None,
        )


def _expiring_clock():
    state = [0.0]

    def clock():
        return state[0]

    def sleep(seconds):
        state[0] += seconds

    return clock, sleep


def test_agree_resume_degraded_quorum_on_min_hosts(tmp_path):
    """Deadline expiry with voters >= min_hosts: the agreement is computed over
    the surviving voter set and flagged degraded — the supervisor's cue to
    shrink the topology instead of failing the resume."""
    ring = tmp_path / "checkpoints"
    _seal(ring, 4)
    ok8 = _seal(ring, 8)
    info_path = _pointer(ring, ok8)
    votes = tmp_path / "votes"
    votes.mkdir()
    atomic_write_json(
        votes / "resume_vote_a0_h2.json", {"host_id": 2, "attempt": 0, "steps": [4, 8]}
    )
    clock, sleep = _expiring_clock()
    agreement = agree_resume(
        info_path, votes, host_id=0, host_count=3, attempt=0, deadline_s=3.0,
        sleep_fn=sleep, clock=clock, min_hosts=2,
    )
    assert agreement.degraded
    assert agreement.voters == [0, 2]  # host 1 is the casualty
    assert agreement.step == 8
    assert agreement.folder == ok8


def test_agree_resume_below_min_hosts_still_fails(tmp_path):
    """min_hosts is a floor, not a bypass: fewer voters than min_hosts at the
    deadline is still a fatal missed quorum."""
    ring = tmp_path / "checkpoints"
    info_path = _pointer(ring, _seal(ring, 4))
    clock, sleep = _expiring_clock()
    with pytest.raises(FileNotFoundError, match="quorum"):
        agree_resume(
            info_path, tmp_path / "votes", host_id=0, host_count=3, attempt=0,
            deadline_s=3.0, sleep_fn=sleep, clock=clock, min_hosts=2,
        )


def test_agree_resume_excludes_burned_steps_from_votes(tmp_path):
    """A burned ladder step must vanish from this host's OWN vote, so the whole
    cluster converges below it."""
    ring = tmp_path / "checkpoints"
    ok4 = _seal(ring, 4)
    ok8 = _seal(ring, 8)
    info_path = _pointer(ring, ok8)
    votes = tmp_path / "votes"
    votes.mkdir()
    atomic_write_json(
        votes / "resume_vote_a1_h1.json", {"host_id": 1, "attempt": 1, "steps": [4, 8]}
    )
    agreement = agree_resume(
        info_path, votes, host_id=0, host_count=2, attempt=1, deadline_s=5.0,
        sleep_fn=lambda s: None, exclude_steps=frozenset({8}),
    )
    assert agreement.step == 4 and agreement.folder == ok4
    vote_0 = json.loads((votes / "resume_vote_a1_h0.json").read_text())
    assert vote_0["steps"] == [4]


# ------------------------------------------------------------- HLO contract


def _consensus_hlo(stop_consensus):
    import jax.numpy as jnp

    from modalities_tpu.loss_functions import CLMCrossEntropyLoss
    from modalities_tpu.optimizers.optimizer_factory import OptimizerFactory
    from modalities_tpu.optimizers.scheduler_factory import DummyLRScheduler
    from modalities_tpu.running_env.device_mesh import get_device_mesh
    from modalities_tpu.training.train_step import TrainStepBuilder
    from tests.models.test_gpt2_model import tiny_gpt2

    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    opt = OptimizerFactory.get_adam_w(
        lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
        weight_decay_groups_excluded=["norm", "embedding"], wrapped_model=model,
    )
    builder = TrainStepBuilder(
        model=model,
        loss_fn=CLMCrossEntropyLoss(target_key="target_ids", prediction_key="logits"),
        optimizer_spec=opt,
        scheduler_spec=DummyLRScheduler(name="dummy", optimizer=opt),
        mesh_handle=mesh,
        gradient_acc_steps=1,
        grad_clip_norm=1.0,
        stop_consensus=stop_consensus,
    )
    fns = builder.build(seed=0)
    tokens = jax.ShapeDtypeStruct((1, 8, 16), jnp.int32)
    abstract = {"samples": {"input_ids": tokens}, "targets": {"target_ids": tokens}}
    if stop_consensus:
        abstract[BALLOT_KEY] = jax.ShapeDtypeStruct((8,), jnp.int32)
    return fns.lower_train_step(abstract).as_text()


def test_consensus_off_hlo_is_byte_identical_and_on_adds_at_most_one_all_reduce():
    baseline = _consensus_hlo(stop_consensus=False)
    off = _consensus_hlo(stop_consensus=False)
    # the acceptance contract: disabled costs literally nothing — the program
    # text of a consensus-capable build is byte-identical to the baseline
    assert off == baseline
    on = _consensus_hlo(stop_consensus=True)
    assert on != baseline
    assert BALLOT_KEY in on
    # the ballot adds AT MOST one replicated scalar reduction to the step
    n_base = baseline.count("all-reduce")
    n_on = on.count("all-reduce")
    assert n_on <= n_base + 1, (n_base, n_on)
