"""Test harness: run everything on CPU with 8 virtual devices so mesh/sharding logic
(dp/tp/pp/cp) is exercised without TPU hardware (SURVEY.md §4 TPU translation)."""

import os

# Force the CPU, with 8 virtual devices for the sharding logic: tests are fast and
# deterministic there, and a test process must never take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"

# How the suite compiles for the CPU (PR 47): its programs are toys that run once or
# twice, so what a test waits for is XLA's compile, not the code it emits. One rule,
# set here before jax is imported, so every worker and every subprocess a test starts
# compiles the same way; a test that hands a subprocess an XLA_FLAGS of its own builds
# it with `xla_flags(devices)`. Chosen by measurement, once (PR 47: the twenty heaviest
# files outside tests/benchmark/, 616 tests at six workers, seconds summed over tests;
# XLA's defaults in the whole run before: 3,679):
#   level 0 and LLVM's expensive passes off   2,925   (kept)
#   level 1 and the expensive passes off      3,447   (and one two-process loss 7e-5 off its oracle)
#   the expensive passes off alone            4,126
# Code compiled at level 0 runs slower than it compiles faster: a test that executes
# much (interpreted kernels over long rows) shrinks what it executes.
CPU_COMPILE_FLAGS = "--xla_backend_optimization_level=0 --xla_llvm_disable_expensive_passes=true"


def xla_flags(devices: int) -> str:
    """The suite's XLA_FLAGS for a process with this many virtual CPU devices."""
    return f"{CPU_COMPILE_FLAGS} --xla_force_host_platform_device_count={devices}"


os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {xla_flags(8)}".strip()  # a later flag wins

import json  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# --- every test's seconds, as they arrive (PR 13; PR 47) -------------------------
# The run that matters most is the one the clock cuts, and a file written at the
# session's end is the one it never writes. So the controller appends a line for
# every phase of every test as its report arrives ({"nodeid", "when", "duration_s",
# "outcome"}: setup holds a module's fixtures, call the test's own), and a cut run
# leaves what it reached. Path: MODALITIES_TPU_TEST_DURATIONS_PATH ("" disables),
# else <rootdir>/test_durations.jsonl. One writer: a worker under pytest-xdist
# writes nothing, its reports reach the controller's hook.

_durations_path: str | None = None


def pytest_sessionstart(session):
    global _durations_path
    raw = os.environ.get("MODALITIES_TPU_TEST_DURATIONS_PATH")
    if raw == "" or hasattr(session.config, "workerinput"):
        _durations_path = None
        return
    _durations_path = raw or str(session.config.rootpath / "test_durations.jsonl")
    try:
        open(_durations_path, "w").close()  # this run's file holds this run
    except OSError:
        _durations_path = None  # an unwritable artifact path must never fail the suite


def pytest_runtest_logreport(report):
    if _durations_path is None:
        return
    row = {"nodeid": report.nodeid, "when": report.when, "duration_s": round(report.duration, 3), "outcome": report.outcome}
    try:
        with open(_durations_path, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError:
        pass


# --- how the suite is dealt to its workers (PR 47) ---------------------------------
# `--dist load` hands a worker consecutive tests in chunks of a quarter of its share (89
# tests at the start of this suite, a twelfth of what is pending after that) and takes
# none back: the worker whose chunks held tests/benchmark/'s rehearsals finished 410 s
# after the other five had nothing left, of a run of 1,215 s (PR 47's measurement; the
# parent's run: 530 of 1,734). Chunks of at most 32 bring the six within 5% of an even
# deal in whatever order the directories come, and still keep most of a file's tests,
# and so its module's fixtures and cached programs, on one worker. pytest-xdist's own
# `--maxschedchunk`, given here because the command that runs the suite is not ours.

_DEAL_AT_MOST = 32


def pytest_configure(config):
    if getattr(config.option, "maxschedchunk", _DEAL_AT_MOST) is None:  # pytest-xdist is loaded and nobody chose
        config.option.maxschedchunk = _DEAL_AT_MOST


# --- no test eats the run's clock (PR 47) ------------------------------------------
# The driver's run has one limit for the whole suite, so one test that hangs takes
# every later test's count with it. The slowest test of PR 46's run took 150 s on a
# busy machine: four times that is a hang, never a slow machine.

_HANG_LIMIT_S = 600


@pytest.fixture(autouse=True)
def _fails_by_name_where_it_hangs(request):
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def on_alarm(signum, frame):
        pytest.fail(f"{request.node.nodeid} ran over {_HANG_LIMIT_S} s: a hang, not a slow machine")

    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(_HANG_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def kernels_interpreted():
    """What this test traces takes every kernel a TPU would take, interpreted (`ops/tiers.interpreted_kernels`)."""
    from modalities_tpu.ops import tiers

    with tiers.interpreted_kernels():
        yield


@pytest.fixture
def tune_table(tmp_path, monkeypatch):
    """`tune_table({"flash_attention|*|*": {"block_q": 64, "block_k": 32}})`: this test's kernels take their blocks from
    a table under MODALITIES_TPU_TUNE_DIR, which is how an operator gives a kernel other blocks than the shipped ones."""
    from modalities_tpu.ops.pallas import autotune

    def plant(entries):
        monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path / "tune"))
        autotune.save_table(tmp_path / "tune", autotune.device_kind_slug(), entries)
        autotune.clear_cache()

    yield plant
    autotune.clear_cache()


@pytest.fixture
def tmp_experiment_dir(tmp_path):
    d = tmp_path / "experiments"
    d.mkdir()
    return d


def start_and_await_first_sweep(router, timeout_s: float = 60.0):
    """`router.start()`, back once its first health round is over: the probes AND the evaluation that follows them, by the
    round's own hook. Five copies of a wait compared heartbeats read AFTER `start()`: beside a busy machine the first sweep
    was over before that reading, the next one `health_interval_s` (30 s) away, and the wait's 5 s ran out on a healthy
    router ('first health sweep never completed': one serving test red a run, never the same one)."""
    swept = threading.Event()
    after_round = router._after_health_round

    def hook():
        after_round()
        swept.set()

    router._after_health_round = hook
    router.start()
    assert swept.wait(timeout_s), f"no health round was over {timeout_s} s after start()"


def make_word_level_tokenizer(vocab: dict, dst, unk_token: str, **special_tokens):
    """Tiny offline WordLevel HF tokenizer saved to `dst` — the shared builder for
    every test that needs a tokenizer without hub access (sft/generate/conversion/
    instruction-tuning e2e). `special_tokens` forwards to PreTrainedTokenizerFast
    (eos_token=..., pad_token=..., bos_token=...)."""
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    tok = tokenizers.Tokenizer(WordLevel(vocab, unk_token=unk_token))
    tok.pre_tokenizer = Whitespace()
    fast = PreTrainedTokenizerFast(tokenizer_object=tok, **special_tokens)
    fast.save_pretrained(dst)
    return fast
