"""The quickest proof that modalities-tpu still starts on the chip.

    python chip_smoke.py            # one chip: `run` trains, `serve` answers
    python chip_smoke.py --chips 4  # one host, four chips: the sharded train step

With no arguments, on a machine with one TPU: `python -m modalities_tpu run` takes
8 optimizer steps of the 2.7B recipe at its published widths (depth and microbatch
cut to the chip, configs/config_smoke_2p7b.yaml) on data packed from --seed and
seals a checkpoint; a second process times the same jitted step under two fences
and shows whether the compile cache hit; `python -m modalities_tpu serve` loads
that checkpoint and answers 8 staggered requests through the paged engine, and one
answer is held to the argmax of a plain `model.apply`. With `--chips 4` it runs the
same recipe under dp_shard 2 x tp 2 in one process driving all four chips, and the
one-chip run of the same global batch it is compared with, and nothing else.

Every phase is a child process of this script that runs the normal entry point
in-process and then looks at what it left behind. A chip belongs to one process at
a time, so this parent never touches `jax`. Without a TPU the first child says so
and the script exits 1 with no result line; there is no CPU run. The last line of a
good run is the one the driver reads, `{"ok": true, "device": {...}}`; the lines
before it are smoke observations of the device they name, not metrics.

The phases take their configs as parameters: tests/test_chip_smoke.py drives them
at toy size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import yaml

REPO = Path(__file__).resolve().parent
TRAIN_CONFIG = REPO / "configs" / "config_smoke_2p7b.yaml"
SERVE_CONFIG = REPO / "configs" / "config_smoke_2p7b_serve.yaml"

# one request per prompt length (tokens); the first is the one held to the reference,
# and 224 + 32 new tokens make a sequence the flash kernel tiles
PROMPT_TOKENS = (224, 32, 64, 128, 256, 384, 512, 96)
ARRIVAL_GAP_S = 0.25
FENCE_STEPS = 5
# |loss_0 - ln V| for random weights. Logits of variance s^2 cost s^2 / 2 above ln V:
# 0.51 at the recipe's head init (std 0.02) and width (2560), where s is 1.01
FIRST_LOSS_BAND = 1.0
# bf16 rounding can swap two logits closer than this; a token the engine chose
# that the reference ranks second by less is a tie, anything else a failure
TIE_LOGIT_GAP = 0.05
# per-step loss, four chips against one: same data and init, bf16 sums in another order
SHARDED_LOSS_RTOL = 5e-3


# ------------------------------------------------------------------ configs


def read_config(path: Path) -> dict:
    """The YAML as written. Never resolved here: `${cuda_env:RANK}` asks jax."""
    return yaml.safe_load(Path(path).read_text())


def derive_config(src: Path, dst: Path, overrides: dict) -> Path:
    """`src` with dotted-path overrides, written to `dst` (interpolations survive
    as the strings they are)."""
    raw = read_config(src)
    for dotted, value in overrides.items():
        node = raw
        *parents, leaf = dotted.split(".")
        for key in parents:
            node = node[int(key)] if isinstance(node, list) else node[key]
        node[leaf] = value
    dst.write_text(yaml.safe_dump(raw, sort_keys=False))
    return dst


def train_shape(train_config: Path) -> dict:
    raw = read_config(train_config)
    profile = raw["settings"]["step_profile"]
    mesh = raw["device_mesh"]["config"]
    return {
        "vocab_size": raw["model_raw"]["config"]["vocab_size"],
        "n_layer": raw["model_raw"]["config"]["n_layer"],
        "sequence_length": profile["sequence_length"],
        "micro_batch": profile["local_train_micro_batch_size"],
        "steps": raw["settings"]["training_target"]["num_target_steps"],
        "sequences_per_step": profile["local_train_micro_batch_size"]
        * profile["gradient_accumulation_steps"]
        * mesh["data_parallel_shard_degree"]
        * mesh["data_parallel_replicate_degree"],
    }


# ------------------------------------------------------------------ phase: probe


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def phase_probe(workdir: Path, args) -> dict:
    return {"device": device_info()}


# ------------------------------------------------------------------ phase: prep


def phase_prep(workdir: Path, args) -> dict:
    """Tokenizer, packed corpus and request file, all from --seed, through the
    repo's own data tools. Runs with the CPU forced: it needs no chip."""
    import numpy as np
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    from modalities_tpu import native
    from modalities_tpu.api import FileExistencePolicy, create_raw_data_index, pack_encoded_data

    library_was_there = native._SO.exists()
    shape = train_shape(args.train_config)
    vocab_size, seq = shape["vocab_size"], shape["sequence_length"]
    data = workdir / "data"
    data.mkdir(parents=True, exist_ok=True)

    # one word per id, as tests/conftest.make_word_level_tokenizer builds it
    vocab = {f"w{i}": i for i in range(vocab_size - 1)}
    vocab["<eod>"] = vocab_size - 1
    tokenizer = Tokenizer(WordLevel(vocab, unk_token="w0"))
    tokenizer.pre_tokenizer = Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tokenizer, unk_token="w0", pad_token="w0", eos_token="<eod>"
    ).save_pretrained(data / "tokenizer")

    rng = np.random.default_rng(args.seed)
    num_docs = shape["steps"] * shape["sequences_per_step"] + 2
    with open(data / "smoke.jsonl", "w") as f:
        for _ in range(num_docs):
            ids = rng.integers(0, vocab_size - 1, size=seq)
            f.write(json.dumps({"text": " ".join(f"w{i}" for i in ids)}) + "\n")
    create_raw_data_index(data / "smoke.jsonl", data / "smoke.idx", FileExistencePolicy.OVERRIDE)
    pack_encoded_data(
        {
            "settings": {
                "src_path": str(data / "smoke.jsonl"),
                "dst_path": str(data / "smoke.pbin"),
                "index_path": str(data / "smoke.idx"),
                "jq_pattern": ".text",
                "num_cpus": 2,
                "eod_token": "<eod>",
                "processing_batch_size": 8,
                "raw_samples_queue_size": 16,
                "processed_samples_queue_size": 16,
            },
            "tokenizer": {
                "component_key": "tokenizer",
                "variant_key": "pretrained_hf_tokenizer",
                "config": {"pretrained_model_name_or_path": str(data / "tokenizer")},
            },
        },
        FileExistencePolicy.OVERRIDE,
    )

    prompt_tokens = [min(n, seq // 2) for n in PROMPT_TOKENS]
    with open(data / "requests.jsonl", "w") as f:
        for i, n in enumerate(prompt_tokens):
            ids = rng.integers(0, vocab_size - 1, size=n)
            row = {
                "prompt": " ".join(f"w{t}" for t in ids),
                "max_new_tokens": args.max_new_tokens,
                "temperature": 0.0,
                "arrival_offset_s": i * ARRIVAL_GAP_S,
            }
            f.write(json.dumps(row) + "\n")
    return {
        # which data path indexed the corpus: the checkout ships data_ops.cpp only
        "data_ops": "python fallback" if native.get_lib() is None
        else "native, library found beside the sources" if library_was_there
        else "native, built here with g++",
        "packed_tokens": num_docs * (seq + 1),
        "prompt_tokens": prompt_tokens,
    }


# ------------------------------------------------------------------ what a jax phase observes


def kernels_in(compiled_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled program, by the `name=` each pallas_call carries."""
    import re

    found: dict[str, int] = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        match = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        name = match.group(1) if match else "unnamed"
        found[name] = found.get(name, 0) + 1
    return found


def peak_memory() -> list[dict]:
    import jax

    rows = []
    for device in jax.devices():
        stats = device.memory_stats()
        if stats:  # the CPU backend keeps none
            rows.append({"device": device.id, "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                         "bytes_limit": stats.get("bytes_limit")})
    return rows


def run_cli(*argv: str) -> None:
    """The entry point a user calls, in this process: what it raises is raised."""
    from modalities_tpu.__main__ import main

    main(list(argv), standalone_mode=False)


# ------------------------------------------------------------------ phase: train


def phase_train(workdir: Path, args) -> dict:
    """`python -m modalities_tpu run --test_comm` on the packed corpus, then: every
    loss finite, the first near ln V, the last checkpoint sealed."""
    from modalities_tpu.resilience.manifest import verify_manifest

    from modalities_tpu.telemetry.compile_log import CompileLog

    compile_log = CompileLog()
    # where the config's own relative paths put results, under the work directory
    experiments = workdir / "data" / "experiments"
    earlier = set(experiments.glob("*/evaluation_results.jsonl"))
    t0 = time.perf_counter()
    run_cli(
        "run", "--config_file_path", str(args.train_config),
        "--experiments_root_path", str(experiments), "--test_comm",
    )
    wall_s = time.perf_counter() - t0

    (results_file,) = set(experiments.glob("*/evaluation_results.jsonl")) - earlier
    records = [json.loads(line) for line in results_file.read_text().splitlines()]
    train = [r for r in records if r["dataloader_tag"] == "train"]
    losses = [r["losses"]["train loss avg"] for r in train]
    shape = train_shape(args.train_config)
    assert len(losses) == shape["steps"] >= 5, (len(losses), shape["steps"])
    assert all(math.isfinite(x) for x in losses), losses
    ln_v = math.log(shape["vocab_size"])
    assert abs(losses[0] - ln_v) < FIRST_LOSS_BAND, (losses[0], ln_v)

    info = json.loads((workdir / "data" / "checkpoints" / "last_checkpoint_info.json").read_text())
    checkpoint = Path(info["checkpoint_folder_path"])
    if not checkpoint.is_absolute():
        checkpoint = workdir / checkpoint
    assert f"seen_steps_{shape['steps']}-" in checkpoint.name, checkpoint
    seal = verify_manifest(checkpoint)
    assert seal.ok, seal.reason
    return {
        "n_layer": shape["n_layer"],
        "micro_batch": shape["micro_batch"],
        "steps": len(losses),
        "losses": [round(x, 4) for x in losses],
        "ln_vocab": round(ln_v, 4),
        # the trainer's own interval clock (one step an interval; the first compiles)
        "s_per_step_trainer": [round(1.0 / r["throughput_metrics"]["train steps/s"], 4) for r in train],
        "wall_s": round(wall_s, 1),
        "checkpoint": str(checkpoint),
        "compiles": compile_log.summary("train_step"),
        "peak_memory": peak_memory(),
    }


# ------------------------------------------------------------------ phase: step


def phase_step(workdir: Path, args) -> dict:
    """A second process over the same jitted step: what the compile cache spares
    it, which kernels the compiled program holds, and seconds per step under
    `jax.block_until_ready` and under `util.hard_sync`."""
    import statistics

    import jax

    from modalities_tpu.dataloader.device_feeder import DeviceFeeder
    from modalities_tpu.main import Main
    from modalities_tpu.running_env.env import configure_compilation_cache
    from modalities_tpu.running_env.xla_flags import apply_xla_flags_from_config
    from modalities_tpu.util import hard_sync

    apply_xla_flags_from_config(args.train_config)  # as `run` does: they are part of the cache key
    cache_dir = configure_compilation_cache()
    from modalities_tpu.telemetry.compile_log import CompileLog

    compile_log = CompileLog()
    main = Main(args.train_config)
    components = main.build_components()
    fns = Main.build_step_functions(components)
    feed = DeviceFeeder(prefetch_to_device=0).feed_train(
        components.train_dataloader, fns.put_batch,
        components.settings.step_profile.gradient_accumulation_steps,
    )
    try:
        batch = next(feed)
    finally:
        feed.close()

    t0 = time.perf_counter()
    compiled = fns.lower_train_step(batch).compile()
    aot_compile_s = time.perf_counter() - t0
    kernels = kernels_in(compiled.as_text())

    state = fns.app_state_handle.state
    state, metrics = fns.train_step(state, batch)  # the jit's own first call
    first_loss = hard_sync(metrics["loss"])

    def timed(fence) -> list[float]:
        nonlocal state
        seconds = []
        for _ in range(FENCE_STEPS):
            t = time.perf_counter()
            state, metrics = fns.train_step(state, batch)
            fence(state, metrics)
            seconds.append(time.perf_counter() - t)
        return seconds

    by_block = timed(lambda state, metrics: jax.block_until_ready((state, metrics)))
    by_fetch = timed(lambda state, metrics: hard_sync(metrics["loss"]))
    assert math.isfinite(first_loss), first_loss
    return {
        "cache_dir": cache_dir,
        "lower_and_compile_s": round(aot_compile_s, 2),
        "compiles": compile_log.summary("train_step"),
        "kernels": kernels,
        "s_per_step_block_until_ready": [round(s, 4) for s in by_block],
        "s_per_step_hard_sync": [round(s, 4) for s in by_fetch],
        "median_s_block_until_ready": round(statistics.median(by_block), 4),
        "median_s_hard_sync": round(statistics.median(by_fetch), 4),
        "peak_memory": peak_memory(),
    }


# ------------------------------------------------------------------ phase: serve


def phase_serve(workdir: Path, args) -> dict:
    """`python -m modalities_tpu serve` from the checkpoint `run` sealed, then: every
    row answered, one answer against a plain `model.apply`, one decode executable,
    no block left held."""
    import logging

    import jax.numpy as jnp
    import numpy as np

    from modalities_tpu.config.yaml_interp import load_app_config_dict
    from modalities_tpu.serving.serve import build_serving_components, load_serving_params

    from modalities_tpu.telemetry.compile_log import CompileLog

    compile_log = CompileLog()
    checkpoint = json.loads((workdir / "phase_train.json").read_text())["checkpoint"]
    config = derive_config(
        args.serve_config, workdir / "config_serve.yaml", {"settings.checkpoint_folder_path": checkpoint}
    )
    stats_lines: list[str] = []

    class Stats(logging.Handler):
        def emit(self, record):
            if str(record.msg).startswith("serve stats:"):
                stats_lines.append(record.getMessage())

    serve_logger = logging.getLogger("modalities_tpu.serving.serve")
    handler = Stats()
    serve_logger.addHandler(handler)
    prior_level = serve_logger.level
    serve_logger.setLevel(logging.INFO)
    results = workdir / "data" / "results.jsonl"
    t0 = time.perf_counter()
    try:
        run_cli(
            "serve", "--config_file_path", str(config),
            "--requests_file_path", str(workdir / "data" / "requests.jsonl"),
            "--output_file_path", str(results),
        )
    finally:
        serve_logger.removeHandler(handler)
        serve_logger.setLevel(prior_level)
    wall_s = time.perf_counter() - t0

    rows = [json.loads(line) for line in results.read_text().splitlines()]
    requests = [json.loads(line) for line in (workdir / "data" / "requests.jsonl").read_text().splitlines()]
    assert len(rows) == len(requests) == len(PROMPT_TOKENS), (len(rows), len(requests))
    for row, request in zip(rows, requests):
        assert row["finish_reason"] in ("budget", "eod"), row["finish_reason"]
        assert 1 <= len(row["tokens"]) <= request["max_new_tokens"], len(row["tokens"])
        assert row["finish_reason"] == "eod" or len(row["tokens"]) == request["max_new_tokens"]
    (stats_line,) = stats_lines
    stats = json.loads(stats_line.split("serve stats:", 1)[1])
    assert stats["decode_executables"] == 1, stats
    assert stats["free_blocks"] == stats["num_blocks"], stats
    assert stats["request_errors"] == 0, stats
    served = compile_log.summary("prefill", "decode")

    # cache path against no-cache path, in this process, on this device: one forward
    # over prompt + answer, whose argmax at each position is the next greedy token
    components = build_serving_components(load_app_config_dict(config))
    component = components.serving_component
    params = load_serving_params(checkpoint)
    prompt = list(component.tokenizer.tokenize(requests[0]["prompt"]))
    answer = rows[0]["tokens"]
    tokens = jnp.asarray([prompt + answer], dtype=jnp.int32)
    logits = component.model.apply(params, {component.model.sample_key: tokens})[
        component.model.prediction_key
    ]
    logits = np.asarray(logits[0, len(prompt) - 1:-1].astype(jnp.float32))
    assert np.isfinite(logits).all() and logits.shape[0] == len(answer), logits.shape
    reference = logits.argmax(-1)
    gaps = logits.max(-1) - logits[np.arange(len(answer)), answer]
    exact = int((reference == np.asarray(answer)).sum())
    assert (gaps <= TIE_LOGIT_GAP).all(), (
        f"engine tokens leave the reference's argmax by {gaps.max():.4f} logits "
        f"(tie allowance {TIE_LOGIT_GAP}); exact {exact}/{len(answer)}"
    )

    component.params = params
    decode_kernels = kernels_in(component.build_engine().decode_compiled_text())
    return {
        "requests": len(rows),
        "tokens_served": sum(len(row["tokens"]) for row in rows),
        "finish_reasons": sorted({row["finish_reason"] for row in rows}),
        "wall_s": round(wall_s, 1),
        "ttft_s": [round(row["ttft_s"], 3) for row in rows],
        "latency_s": [round(row["latency_s"], 3) for row in rows],
        "engine_stats": {k: stats[k] for k in (
            "decode_executables", "prefill_executables", "decode_steps", "decode_tokens",
            "max_concurrent", "preemptions", "num_blocks", "free_blocks", "kv_pool_bytes",
        )},
        "compiles_while_serving": served,
        "reference": {
            "tokens": len(answer), "exact_argmax": exact,
            "largest_gap_logits": round(float(gaps.max()), 4), "tie_allowance": TIE_LOGIT_GAP,
        },
        "decode_kernels": decode_kernels,
        "peak_memory": peak_memory(),
    }


# ------------------------------------------------------------------ phase: sharded (four chips)


def phase_sharded(workdir: Path, args) -> dict:
    """The train phase under dp_shard 2 x tp 2, one process over four chips, then
    where one large parameter and each device's memory really ended up."""
    import jax

    from modalities_tpu.main import Main

    result = phase_train(workdir, args)
    main = Main(args.train_config)
    fns = Main.build_step_functions(main.build_components())
    leaves = jax.tree_util.tree_leaves_with_path(fns.app_state_handle.state.params)
    path, largest = max(leaves, key=lambda kv: kv[1].size)
    shards = largest.addressable_shards
    devices = sorted({shard.device.id for shard in shards})
    assert len(devices) == len(jax.devices()) == 4, devices
    assert all(shard.data.size * 4 == largest.size for shard in shards), [s.data.shape for s in shards]
    result["largest_param"] = {
        "path": jax.tree_util.keystr(path),
        "shape": list(largest.shape),
        "spec": str(largest.sharding.spec),
        "shard_shapes": sorted({tuple(shard.data.shape) for shard in shards}),
        "devices": devices,
    }
    result["peak_memory"] = peak_memory()
    assert len(result["peak_memory"]) == 4 and all(row["peak_bytes_in_use"] for row in result["peak_memory"])
    return result


# ------------------------------------------------------------------ driver


JAX_PHASES = {"probe": phase_probe, "train": phase_train, "step": phase_step,
              "serve": phase_serve, "sharded": phase_sharded}
PHASES = {"prep": phase_prep, **JAX_PHASES}


def _run_phase(name: str, workdir: Path, args) -> dict:
    """Child side: run one phase, leave its result in the work directory. Only the
    probe asks jax for its devices before anything else: `run` sets the config's
    LIBTPU_INIT_ARGS first, and the runtime reads them once, at start-up."""
    os.chdir(workdir)
    if name == "probe" and device_info()["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU here (jax found {device_info()}); there is no CPU run")
    result = PHASES[name](workdir, args)
    if name in JAX_PHASES:
        result["device"] = device_info()
        assert result["device"]["platform"] == "tpu", result["device"]
    (workdir / f"phase_{args.result_name or name}.json").write_text(json.dumps(result))
    return result


def _child(name: str, workdir: Path, args, *, train_config: Path | None = None,
           result_name: str | None = None, env: dict | None = None) -> dict:
    """Parent side: one phase in its own process, to its end, before the next."""
    command = [
        sys.executable, str(Path(__file__).resolve()), "--phase", name, "--workdir", str(workdir),
        "--seed", str(args.seed), "--max_new_tokens", str(args.max_new_tokens),
        "--train_config", str(train_config or args.train_config),
        "--serve_config", str(args.serve_config),
    ]
    if result_name:
        command += ["--result_name", result_name]
    t0 = time.perf_counter()
    code = subprocess.run(command, env={**os.environ, **(env or {})}).returncode
    if code != 0:
        raise SystemExit(f"chip_smoke: phase {name} failed (exit {code})")
    result = json.loads((workdir / f"phase_{result_name or name}.json").read_text())
    print(f"[{result_name or name}] {time.perf_counter() - t0:.0f}s {json.dumps(result)}", flush=True)
    return result


EXPECTED_TRAIN_KERNELS = (
    "flash_attention_fwd", "flash_attention_bwd",
    "fused_ce_fwd", "fused_ce_bwd_dw", "fused_rmsnorm_fwd", "fused_rmsnorm_bwd",
)
# decode attends over the paged table with plain XLA ops; its norms are the kernel
EXPECTED_DECODE_KERNELS = ("fused_rmsnorm_fwd",)


def _require_kernels(program: str, expected: tuple, found: dict) -> None:
    missing = [kernel for kernel in expected if kernel not in found]
    assert not missing, f"compiled {program} lacks {missing}: {found}"


def one_chip(workdir: Path, args) -> None:
    shape = train_shape(args.train_config)
    print(f"cuts of configs/config_2p7b_dp.yaml: n_layer 32 -> {shape['n_layer']}, "
          f"micro batch 4 -> {shape['micro_batch']}, mesh -> one chip", flush=True)
    _child("prep", workdir, args, env={"JAX_PLATFORMS": "cpu"})
    train = _child("train", workdir, args)
    step = _child("step", workdir, args)
    _require_kernels("train step", EXPECTED_TRAIN_KERNELS, step["kernels"])
    first, second = train["compiles"]["train_step"], step["compiles"]["train_step"]
    print(f"train step compile: {first['first_s']}s in `run` "
          f"({'the cache came warm with the machine' if first['cache_hits'] else 'cold'}), "
          f"{second['first_s']}s in the second process ({second['cache_hits']} of {second['count']} "
          f"from the cache at {step['cache_dir']})", flush=True)
    assert second["cache_hits"] == second["count"] >= 1, step["compiles"]
    serve = _child("serve", workdir, args)
    _require_kernels("decode step", EXPECTED_DECODE_KERNELS, serve["decode_kernels"])


def four_chips(workdir: Path, args) -> None:
    shape = train_shape(args.train_config)
    assert shape["micro_batch"] % 2 == 0, shape
    sharded_config = derive_config(args.train_config, workdir / "config_sharded.yaml", {
        "device_mesh.config.data_parallel_shard_degree": 2,
        "device_mesh.config.tensor_parallel_degree": 2,
        "device_mesh.config.world_size": 4,
        "settings.step_profile.local_train_micro_batch_size": shape["micro_batch"] // 2,
    })
    _child("prep", workdir, args, env={"JAX_PLATFORMS": "cpu"})
    sharded = _child("sharded", workdir, args, train_config=sharded_config)
    single = _child("train", workdir, args, result_name="single")
    worst = max(abs(a - b) / abs(b) for a, b in zip(sharded["losses"], single["losses"]))
    print(f"dp_shard 2 x tp 2 against one chip, same global batch: largest relative "
          f"loss difference {worst:.2e} over {len(single['losses'])} steps (tolerance {SHARDED_LOSS_RTOL})", flush=True)
    assert len(sharded["losses"]) == len(single["losses"]) and worst <= SHARDED_LOSS_RTOL, (
        sharded["losses"], single["losses"])


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--workdir", type=Path, default=REPO / "data" / "chip_smoke",
                        help="scratch for corpus, checkpoint and results; removed after a good run")
    parser.add_argument("--train_config", type=Path, default=TRAIN_CONFIG)
    parser.add_argument("--serve_config", type=Path, default=SERVE_CONFIG)
    parser.add_argument("--max_new_tokens", type=int, default=32)
    parser.add_argument("--phase", choices=sorted(PHASES), help="internal: run one phase in this process")
    parser.add_argument("--result_name", help="internal")
    args = parser.parse_args()
    import modalities_tpu  # noqa: F401  the script alone, without the program, fails here

    workdir = args.workdir.resolve()
    args.train_config, args.serve_config = args.train_config.resolve(), args.serve_config.resolve()
    if args.phase:
        _run_phase(args.phase, workdir, args)
        return
    import shutil

    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    device = _child("probe", workdir, args)["device"]
    if device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} on a machine with {device['count']}")
    (one_chip if args.chips == 1 else four_chips)(workdir, args)
    shutil.rmtree(workdir)  # a failed run keeps it, for whoever looks into the failure
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
