"""CLI (reference: src/modalities/__main__.py — click command tree with run, warmstart,
generate_text, data tools, benchmark sweeps, profiling, plus per-rank structured JSON
error logs, :726-749)."""

from __future__ import annotations

import json
import os
import socket
import sys
import traceback
from datetime import datetime
from pathlib import Path
from typing import Optional

import functools

import click

from modalities_tpu.api import FileExistencePolicy
from modalities_tpu.resilience.errors import RESUMABLE_EXIT_CODE, ResumableError
from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _exception_handling(func):
    """Write a per-rank structured JSON error log next to stderr (reference :736).
    A `ResumableError` (preemption, anomaly rollback) maps to the distinguished
    `RESUMABLE_EXIT_CODE` so a supervisor can tell "warmstart me" from a crash."""

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        try:
            return func(*args, **kwargs)
        except Exception as e:
            rank = int(os.environ.get("RANK", 0))
            error_record = {
                "rank": rank,
                "hostname": socket.gethostname(),
                "timestamp": datetime.now().isoformat(),
                "error": repr(e),
                "resumable": isinstance(e, ResumableError),
                "stacktrace": traceback.format_exc(),
            }
            error_dir = Path(os.environ.get("MODALITIES_TPU_ERROR_LOG_DIR", "."))
            error_dir.mkdir(parents=True, exist_ok=True)
            error_file = error_dir / f"error_rank_{rank}.json"
            with open(error_file, "w") as f:
                json.dump(error_record, f, indent=2)
            if isinstance(e, ResumableError):
                logger.warning(
                    "Run stopped resumably (%s); exiting %d for the supervisor. "
                    "Error log: %s", e, RESUMABLE_EXIT_CODE, error_file,
                )
                raise SystemExit(RESUMABLE_EXIT_CODE) from e
            logger.error("Run failed; error log written to %s", error_file)
            raise

    return wrapper


@click.group()
def main() -> None:
    """modalities-tpu: TPU-native distributed LLM training."""


@main.command(name="run")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--experiments_root_path", type=click.Path(path_type=Path), default=None)
@click.option("--test_comm", is_flag=True, default=False, help="Run a pre-flight collective check.")
@click.option("--resilient", is_flag=True, default=False,
              help="Supervise the run: auto-warmstart on resumable exits (preemption, rollback).")
@click.option("--last_checkpoint_info_file_path", type=click.Path(path_type=Path), default=None,
              help="Where the resume pointer lives/will appear (required with --resilient).")
@click.option("--max_restarts", type=int, default=3, show_default=True,
              help="Crash-loop cap for --resilient.")
@click.option("--backoff_base_s", type=float, default=1.0, show_default=True,
              help="Exponential-backoff base between --resilient restarts.")
@click.option("--warmstart_config_file_path", type=click.Path(exists=True, path_type=Path),
              default=None,
              help="Config the --resilient supervisor uses for resume children; a cold "
              "config pins progress at zero, so most runs need a distinct warmstart YAML.")
@click.option("--host_count", type=int, default=1, show_default=True,
              help="Number of hosts running a --resilient supervisor; >1 enables the "
              "cross-host resume vote (resume target must verify on a quorum of hosts).")
@click.option("--host_id", type=int, default=0, show_default=True,
              help="This host's index in [0, host_count) for the resume vote.")
@click.option("--resume_quorum", type=int, default=None,
              help="Hosts that must vote before resuming (default: all of host_count).")
@click.option("--resume_vote_deadline_s", type=float, default=120.0, show_default=True,
              help="How long a --resilient supervisor waits for the resume quorum.")
@click.option("--coordination_dir_path", type=click.Path(path_type=Path), default=None,
              help="Shared directory for resume vote files (default: a supervisor_votes "
              "folder next to the resume pointer).")
@click.option("--min_hosts", type=int, default=None,
              help="Elastic repair: if the resume vote deadline expires with fewer voters "
              "than the quorum but at least this many, resume anyway on the surviving "
              "hosts with a recomputed (shrunk) mesh. Default: disabled (missed quorum "
              "fails the resume).")
@_exception_handling
def entry_point_run(
    config_file_path: Path,
    experiments_root_path: Optional[Path],
    test_comm: bool,
    resilient: bool,
    last_checkpoint_info_file_path: Optional[Path],
    max_restarts: int,
    backoff_base_s: float,
    warmstart_config_file_path: Optional[Path],
    host_count: int,
    host_id: int,
    resume_quorum: Optional[int],
    resume_vote_deadline_s: float,
    coordination_dir_path: Optional[Path],
    min_hosts: Optional[int],
) -> None:
    """Train from a YAML config."""
    if resilient:
        if last_checkpoint_info_file_path is None:
            raise click.UsageError("--resilient requires --last_checkpoint_info_file_path")
        from modalities_tpu.resilience.supervisor import run_resilient

        code = run_resilient(
            config_file_path=config_file_path,
            last_checkpoint_info_file_path=last_checkpoint_info_file_path,
            experiments_root_path=experiments_root_path,
            warmstart_config_file_path=warmstart_config_file_path,
            max_restarts=max_restarts,
            backoff_base_s=backoff_base_s,
            host_count=host_count,
            host_id=host_id,
            resume_quorum=resume_quorum,
            resume_vote_deadline_s=resume_vote_deadline_s,
            coordination_dir=coordination_dir_path,
            min_hosts=min_hosts,
        )
        if code != 0:
            raise SystemExit(code)
        return

    from modalities_tpu.main import Main
    from modalities_tpu.running_env.env import TpuEnv
    from modalities_tpu.running_env.xla_flags import apply_xla_flags_from_config
    from modalities_tpu.utils.communication_test import run_communication_test

    # performance flags must land before the first backend touch inside TpuEnv
    apply_xla_flags_from_config(config_file_path)
    with TpuEnv():
        if test_comm:
            run_communication_test()
        main_obj = Main(config_file_path, experiments_root_path=experiments_root_path)
        components = main_obj.build_components()
        main_obj.run(components)


@main.command(name="warmstart")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option(
    "--last_checkpoint_info_file_path", type=click.Path(exists=True, path_type=Path), required=True
)
@click.option("--experiments_root_path", type=click.Path(path_type=Path), default=None)
@_exception_handling
def entry_point_warmstart(
    config_file_path: Path, last_checkpoint_info_file_path: Path, experiments_root_path: Optional[Path]
) -> None:
    """Resume from the last checkpoint (reference __main__.py:112-163: injects the
    ${warmstart_env:checkpoint_paths} resolver from last_checkpoint_info.json).

    The resume folder is resolved and VERIFIED here, before config build, because
    the folder name is the metadata store (steps/tokens/sampler position are
    parsed from it): if the pointer's target fails its manifest, the ring is
    walked back to the newest verifiable folder."""
    from modalities_tpu.main import Main
    from modalities_tpu.resilience.manifest import resolve_resume_folder
    from modalities_tpu.running_env.env import TpuEnv
    from modalities_tpu.running_env.xla_flags import apply_xla_flags_from_config

    apply_xla_flags_from_config(config_file_path)
    resume_folder = str(resolve_resume_folder(last_checkpoint_info_file_path))

    def warmstart_env(key: str):
        if key in ("checkpoint_paths", "checkpoint_folder_path"):
            return resume_folder
        raise ValueError(f"Unknown warmstart_env variable {key!r}")

    with TpuEnv():
        main_obj = Main(
            config_file_path,
            experiments_root_path=experiments_root_path,
            additional_resolver_funs={"warmstart_env": warmstart_env},
        )
        components = main_obj.build_components()
        main_obj.run(components)


@main.command(name="generate_text")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@_exception_handling
def entry_point_generate_text(config_file_path: Path) -> None:
    from modalities_tpu.api import generate_text

    generate_text(config_file_path)


@main.command(name="serve")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option(
    "--requests_file_path",
    type=click.Path(exists=True, path_type=Path),
    default=None,
    help="JSONL of requests to replay through the continuous-batching engine; omit for an interactive loop.",
)
@click.option("--output_file_path", type=click.Path(path_type=Path), default=None)
@click.option(
    "--http_port",
    type=int,
    default=None,
    help="Start the streaming HTTP front end (SSE POST /generate, GET /healthz, GET /stats) "
    "on this port (0 = ephemeral) instead of replay/interactive; SIGTERM drains gracefully.",
)
@click.option(
    "--fleet",
    is_flag=True,
    default=False,
    help="Fleet mode (serving/fleet/): N engine workers behind a load-balancing router, "
    "with checkpoint-watcher hot swaps and canary rollouts; the config's "
    "serving_component.variant_key must be 'fleet' (configs/config_fleet.yaml). "
    "--http_port sets the ROUTER port.",
)
@_exception_handling
def entry_point_serve(
    config_file_path: Path,
    requests_file_path: Optional[Path],
    output_file_path: Optional[Path],
    http_port: Optional[int],
    fleet: bool,
) -> None:
    """Continuous-batching text serving (serving/engine.py) from a sealed checkpoint."""
    from modalities_tpu.api import serve_text

    serve_text(
        config_file_path, requests_file_path, output_file_path,
        http_port=http_port, fleet=fleet,
    )


@main.command(name="convert_checkpoint_to_hf")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--output_hf_checkpoint_dir", type=click.Path(path_type=Path), required=True)
@_exception_handling
def entry_point_convert_checkpoint(config_file_path: Path, output_hf_checkpoint_dir: Path) -> None:
    """Export a checkpoint to HuggingFace format (reference convert_pytorch_to_hf_checkpoint)."""
    from modalities_tpu.conversion.gpt2.convert_gpt2 import convert_gpt2

    convert_gpt2(config_file_path, output_hf_checkpoint_dir)


# --------------------------------------------------------------------------- data


@main.group(name="data")
def data() -> None:
    """Data preprocessing tools."""


def _policy(value: str) -> FileExistencePolicy:
    return FileExistencePolicy(value)


@data.command(name="create_raw_index")
@click.argument("src_path", type=click.Path(exists=True, path_type=Path))
@click.option("--index_path", type=click.Path(path_type=Path), default=None)
@click.option("--file_existence_policy", type=click.Choice([p.value for p in FileExistencePolicy]), default="error")
@_exception_handling
def entry_point_create_raw_index(src_path: Path, index_path: Optional[Path], file_existence_policy: str) -> None:
    from modalities_tpu.api import create_raw_data_index

    create_raw_data_index(src_path, index_path, _policy(file_existence_policy))


@data.command(name="pack_encoded_data")
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--file_existence_policy", type=click.Choice([p.value for p in FileExistencePolicy]), default="error")
@_exception_handling
def entry_point_pack_encoded_data(config_path: Path, file_existence_policy: str) -> None:
    from modalities_tpu.api import pack_encoded_data
    from modalities_tpu.config.yaml_interp import load_app_config_dict

    config_dict = load_app_config_dict(config_path)
    pack_encoded_data(config_dict, _policy(file_existence_policy))


@data.command(name="merge_packed_data")
@click.argument("src_paths", type=click.Path(exists=True, path_type=Path), nargs=-1)
@click.argument("target_path", type=click.Path(path_type=Path))
@_exception_handling
def entry_point_merge_packed_data(src_paths: tuple[Path, ...], target_path: Path) -> None:
    from modalities_tpu.api import merge_packed_data_files

    merge_packed_data_files(list(src_paths), target_path)


@data.command(name="shuffle_tokenized_data")
@click.option("--input_data_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--output_data_path", type=click.Path(path_type=Path), required=True)
@click.option("--batch_size", type=int, default=1024)
@click.option("--file_existence_policy", type=click.Choice([p.value for p in FileExistencePolicy]), default="error")
@click.option("--seed", type=int, default=None)
@_exception_handling
def entry_point_shuffle_tokenized_data(
    input_data_path: Path, output_data_path: Path, batch_size: int, file_existence_policy: str, seed: Optional[int]
) -> None:
    from modalities_tpu.api import shuffle_tokenized_data

    shuffle_tokenized_data(input_data_path, output_data_path, batch_size, _policy(file_existence_policy), seed)


@data.command(name="shuffle_jsonl_data")
@click.option("--input_data_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--output_data_path", type=click.Path(path_type=Path), required=True)
@click.option("--file_existence_policy", type=click.Choice([p.value for p in FileExistencePolicy]), default="error")
@click.option("--seed", type=int, default=None)
@_exception_handling
def entry_point_shuffle_jsonl_data(
    input_data_path: Path, output_data_path: Path, file_existence_policy: str, seed: Optional[int]
) -> None:
    from modalities_tpu.api import shuffle_jsonl_data

    shuffle_jsonl_data(input_data_path, output_data_path, _policy(file_existence_policy), seed)


@data.command(name="create_shuffled_dataset_chunk")
@click.option("--input_file_list_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--output_chunk_file_path", type=click.Path(path_type=Path), required=True)
@click.option("--chunk_id", type=int, required=True)
@click.option("--num_chunks", type=int, required=True)
@click.option("--file_existence_policy", type=click.Choice([p.value for p in FileExistencePolicy]), default="error")
@click.option("--global_seed", type=int, default=None)
@_exception_handling
def entry_point_create_shuffled_dataset_chunk(
    input_file_list_path: Path,
    output_chunk_file_path: Path,
    chunk_id: int,
    num_chunks: int,
    file_existence_policy: str,
    global_seed: Optional[int],
) -> None:
    from modalities_tpu.api import create_shuffled_dataset_chunk

    file_list = [Path(line.strip()) for line in input_file_list_path.read_text().splitlines() if line.strip()]
    create_shuffled_dataset_chunk(
        file_list, output_chunk_file_path, chunk_id, num_chunks, _policy(file_existence_policy), global_seed
    )


@data.command(name="create_shuffled_jsonl_chunk")
@click.option("--input_file_list_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--output_chunk_file_path", type=click.Path(path_type=Path), required=True)
@click.option("--chunk_id", type=int, required=True)
@click.option("--num_chunks", type=int, required=True)
@click.option("--file_existence_policy", type=click.Choice([p.value for p in FileExistencePolicy]), default="error")
@click.option("--global_seed", type=int, default=None)
@_exception_handling
def entry_point_create_shuffled_jsonl_chunk(
    input_file_list_path: Path,
    output_chunk_file_path: Path,
    chunk_id: int,
    num_chunks: int,
    file_existence_policy: str,
    global_seed: Optional[int],
) -> None:
    from modalities_tpu.api import create_shuffled_jsonl_dataset_chunk

    file_list = [Path(line.strip()) for line in input_file_list_path.read_text().splitlines() if line.strip()]
    create_shuffled_jsonl_dataset_chunk(
        file_list, output_chunk_file_path, chunk_id, num_chunks, _policy(file_existence_policy), global_seed
    )


@data.command(name="prepare_instruction_tuning_data")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@_exception_handling
def entry_point_prepare_instruction_tuning_data(config_file_path: Path) -> None:
    from modalities_tpu.dataloader.instruction_tuning.create_instruction_tuning_data import (
        create_instruction_tuning_data,
    )

    create_instruction_tuning_data(config_file_path)


@data.command(name="analyze_debug_logs")
@click.option("--log_file_path", type=click.Path(exists=True, path_type=Path), required=True,
              help="A debug_stats_rank_N.jsonl written by DebugStatsLogger.")
@click.option("--step", type=int, default=None, help="Filter to one training step.")
@click.option("--tree", type=str, default=None, help="Filter to one tree (params/grads/...).")
@click.option("--sort_by", type=str, default="max", show_default=True)
@click.option("--ascending", is_flag=True, default=False)
@click.option("--top", type=int, default=20, show_default=True)
@click.option("--nonfinite_only", is_flag=True, default=False,
              help="Only tensors with nan/inf counts > 0.")
@click.option("--as_json", is_flag=True, default=False, help="Emit jsonl rows instead of a table.")
@_exception_handling
def entry_point_analyze_debug_logs(
    log_file_path: Path, step: Optional[int], tree: Optional[str], sort_by: str,
    ascending: bool, top: int, nonfinite_only: bool, as_json: bool,
) -> None:
    """Per-tensor stats triage over a DebugStatsLogger jsonl stream — the CLI
    equivalent of the reference's debug-log analysis notebook
    (notebooks/debug_logs_analysis/model_step_analyser.ipynb)."""
    from modalities_tpu.utils.debug_components import analyze_debug_log, format_debug_log_rows

    rows = analyze_debug_log(
        log_file_path, step=step, tree=tree, sort_by=sort_by, ascending=ascending,
        top=top, nonfinite_only=nonfinite_only,
    )
    if as_json:
        for r in rows:
            click.echo(json.dumps(r))
    else:
        click.echo(format_debug_log_rows(rows))


@data.command(name="analyze_telemetry")
@click.option("--sink_path", type=click.Path(exists=True, path_type=Path), required=True,
              help="A telemetry_rank_N.jsonl file, or the telemetry folder holding them.")
@click.option("--as_json", is_flag=True, default=False, help="Emit the summary dict as JSON.")
@_exception_handling
def entry_point_analyze_telemetry(sink_path: Path, as_json: bool) -> None:
    """Summarize a run's telemetry JSONL sink into a per-rank goodput table:
    every wall-clock second attributed to a bucket (init, compile, train_step,
    data_stall, eval, checkpoint, publish, other) plus goodput %."""
    from modalities_tpu.telemetry.goodput import (
        format_goodput_table,
        format_straggler_table,
        straggler_summary,
        summarize_sink,
    )
    from modalities_tpu.telemetry.waterfall import (
        format_waterfall_table,
        last_waterfall_from_sink,
    )

    summary = summarize_sink(sink_path)
    stragglers = straggler_summary(summary)
    waterfall = last_waterfall_from_sink(sink_path)
    if as_json:
        click.echo(json.dumps({**summary, "stragglers": stragglers, "mfu_waterfall": waterfall}))
    else:
        click.echo(format_goodput_table(summary))
        if len(summary.get("ranks", {})) > 1:
            click.echo("\nstragglers (slowest rank per bucket):")
            click.echo(format_straggler_table(stragglers))
        if waterfall is not None:
            click.echo("\nMFU waterfall (peak -> achieved, deductions close the gap exactly):")
            click.echo(format_waterfall_table(waterfall))


@data.command(name="analyze_serve")
@click.option("--sink_path", type=click.Path(exists=True, path_type=Path), required=True,
              help="A telemetry_rank_N.jsonl file, or the telemetry folder holding them "
                   "(a serve run writes them when MODALITIES_TPU_SERVE_TELEMETRY_DIR is set).")
@click.option("--as_json", is_flag=True, default=False, help="Emit the summary dict as JSON.")
@_exception_handling
def entry_point_analyze_serve(sink_path: Path, as_json: bool) -> None:
    """Summarize a serve run's per-request trace records: p50/p95/p99 tables for
    TTFT, end-to-end latency, queue wait, and mean TPOT; finish-reason
    breakdown; preemption/truncation totals; and a slot-occupancy timeline
    rebuilt from the admission intervals."""
    from modalities_tpu.serving.analyze import (
        format_serve_table,
        load_serve_records,
        summarize_serve,
    )

    summary = summarize_serve(load_serve_records(sink_path))
    if as_json:
        click.echo(json.dumps(summary))
    else:
        click.echo(format_serve_table(summary))


@data.command(name="analyze_perfscope")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True,
              help="Training config; its jitted step is lowered + compiled on virtual "
                   "CPU devices and the optimized HLO is cost-bucketed by op class.")
@click.option("--report_path", type=click.Path(path_type=Path), default=None,
              help="Also write the report JSON here (e.g. perfscope.json).")
@click.option("--as_json", is_flag=True, default=False, help="Emit the report dict as JSON.")
@_exception_handling
def entry_point_analyze_perfscope(
    config_file_path: Path, report_path: Optional[Path], as_json: bool
) -> None:
    """Static performance attribution: where the compiled train step's
    FLOPs/bytes/estimated time go — matmul vs custom-call (flash/Pallas) vs
    collectives per mesh axis vs host transfers vs elementwise. Per-bucket costs
    sum to the module total by construction. Runs entirely on CPU."""
    from modalities_tpu.telemetry.perfscope import (
        format_perfscope_table,
        run_perfscope_subprocess,
        write_report,
    )

    report = run_perfscope_subprocess(config_file_path)
    if report_path is not None:
        write_report(report, report_path)
    if as_json:
        click.echo(json.dumps(report))
    else:
        click.echo(format_perfscope_table(report))


@data.command(name="analyze_memscope")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True,
              help="Training config; its jitted step is lowered + compiled on virtual "
                   "CPU devices and memory_analysis() is carved into semantic buckets.")
@click.option("--report_path", type=click.Path(path_type=Path), default=None,
              help="Also write the report JSON here (e.g. memscope.json).")
@click.option("--as_json", is_flag=True, default=False, help="Emit the report dict as JSON.")
@_exception_handling
def entry_point_analyze_memscope(
    config_file_path: Path, report_path: Optional[Path], as_json: bool
) -> None:
    """Static memory attribution: where the compiled train step's HBM bytes go —
    params vs optimizer moments vs gradients vs activations/workspace vs KV pool
    — with the static estimate beside the runtime peak and headroom when the
    backend reports memory stats. Bucket sums equal the memory_analysis() totals
    by construction. Runs entirely on CPU."""
    from modalities_tpu.telemetry.memscope import (
        format_memscope_table,
        run_memscope_subprocess,
        write_report,
    )

    report = run_memscope_subprocess(config_file_path)
    if report_path is not None:
        write_report(report, report_path)
    if as_json:
        click.echo(json.dumps(report))
    else:
        click.echo(format_memscope_table(report))


@data.command(name="analyze_fleet")
@click.option("--sink_path", "sink_paths", type=click.Path(exists=True, path_type=Path),
              required=True, multiple=True,
              help="Router and/or worker telemetry sinks (files or folders); repeatable "
                   "— pass the router's sink AND each worker's to stitch the full tree.")
@click.option("--as_json", is_flag=True, default=False, help="Emit stitched traces as JSON.")
@_exception_handling
def entry_point_analyze_fleet(sink_paths: tuple[Path, ...], as_json: bool) -> None:
    """Stitch fleet-wide request traces: join the router's `fleet/request`
    records with every worker's `serve_request` records on trace_id and render
    one cross-tier span tree per request — a failover shows up as one trace
    with two worker legs sharing the id."""
    from modalities_tpu.serving.analyze import (
        format_fleet_trace_tree,
        load_fleet_records,
        stitch_fleet_traces,
    )

    traces = stitch_fleet_traces(load_fleet_records(sink_paths))
    if as_json:
        click.echo(json.dumps(traces))
    else:
        click.echo(format_fleet_trace_tree(traces))


@data.command(name="check_slo")
@click.option("--slo_path", type=click.Path(exists=True, path_type=Path), required=True,
              help="YAML SLO spec (same grammar as the telemetry/serving `slo:` block).")
@click.option("--sink_path", "sink_paths", type=click.Path(exists=True, path_type=Path),
              multiple=True,
              help="Telemetry JSONL sink (file or folder); repeatable. serve_request "
                   "traces rebuild the serve_* histograms, mfu_waterfall records the "
                   "training_mfu_achieved gauge, spans the goodput ratio.")
@click.option("--memscope_path", "memscope_paths", type=click.Path(exists=True, path_type=Path),
              multiple=True,
              help="memscope.json static report; repeatable. Buckets become "
                   "memscope_bucket_bytes{executable,bucket} gauges (timeline sink "
                   "events replay via --sink_path).")
@click.option("--as_json", is_flag=True, default=False, help="Emit the verdict dict as JSON.")
@_exception_handling
def entry_point_check_slo(
    slo_path: Path, sink_paths: tuple[Path, ...], memscope_paths: tuple[Path, ...],
    as_json: bool,
) -> None:
    """Evaluate recorded runs against a declarative SLO spec: replay telemetry
    sinks and memscope reports into one metrics registry, judge each objective
    point-in-time (no burn windows — the data is historical), and exit nonzero
    when any objective breaches. The CI face of the live SLO engine."""
    from modalities_tpu.telemetry.metrics import MetricsRegistry
    from modalities_tpu.telemetry.slo import (
        evaluate_recorded,
        load_slo_spec,
        replay_memscope_into_registry,
        replay_sink_into_registry,
    )

    registry = MetricsRegistry()
    replayed = 0
    for path in sink_paths:
        replayed += replay_sink_into_registry(path, registry)
    for path in memscope_paths:
        replayed += replay_memscope_into_registry(path, registry)
    objectives, _ = load_slo_spec(slo_path)
    report = evaluate_recorded(objectives, registry)
    report["records_replayed"] = replayed
    if as_json:
        click.echo(json.dumps(report))
    else:
        width = max(len(o.name) for o in objectives)
        for objective in objectives:
            value = report["values"].get(objective.name)
            if objective.name in report["breaching"]:
                verdict = "BREACH"
            elif objective.name in report["skipped"]:
                verdict = "skipped (no data)"
            else:
                verdict = "ok"
            shown = f"{value:.6g}" if value is not None else "-"
            click.echo(f"{objective.name:<{width}}  {shown:>12}  {verdict}  ({objective.expr})")
        click.echo(
            f"{len(objectives)} objectives over {replayed} replayed records: "
            + ("BREACHING: " + ", ".join(report["breaching"]) if report["breaching"] else "all ok")
        )
    if report["breaching"]:
        raise SystemExit(1)


@data.command(name="tune_kernels")
@click.option("--out_dir", type=click.Path(path_type=Path), default=None,
              help="Where to write {device_kind}.json (default: $MODALITIES_TPU_TUNE_DIR, "
                   "else ./tuning_tables). Point MODALITIES_TPU_TUNE_DIR here so training "
                   "consults the result.")
@click.option("--rows", type=int, default=4096, show_default=True,
              help="Flattened token rows (batch*seq) for the fused-CE/RMSNorm shapes.")
@click.option("--n_embd", type=int, default=1024, show_default=True)
@click.option("--vocab_size", type=int, default=16384, show_default=True)
@click.option("--seq_len", type=int, default=2048, show_default=True,
              help="Sequence length for the flash-attention sweep.")
@click.option("--dtype", type=str, default="bfloat16", show_default=True)
@click.option("--iters", type=int, default=3, show_default=True, help="Best-of-N timing repeats.")
@click.option("--smoke", is_flag=True, default=False,
              help="Tiny shapes (CI / CPU interpret): exercises the round-trip, not the timings.")
@click.option("--as_json", is_flag=True, default=False, help="Emit the full summary dict as JSON.")
@_exception_handling
def entry_point_tune_kernels(
    out_dir: Optional[Path], rows: int, n_embd: int, vocab_size: int, seq_len: int,
    dtype: str, iters: int, smoke: bool, as_json: bool,
) -> None:
    """Timed block-size sweep for the Pallas kernels (flash attention, fused CE,
    fused RMSNorm); persists the winners to a per-device-kind JSON tuning table
    that the dispatch wrappers consult at trace time (env var > tune dir >
    shipped defaults — see docs/components.md). Off-TPU the sweep runs under the
    interpret emulator: the table round-trips but the timings are smoke only."""
    from modalities_tpu.ops.pallas.autotune import tune_kernels

    resolved_out = out_dir or Path(os.environ.get("MODALITIES_TPU_TUNE_DIR") or "tuning_tables")
    summary = tune_kernels(
        out_dir=resolved_out, rows=rows, n_embd=n_embd, vocab_size=vocab_size,
        seq_len=seq_len, dtype=dtype, iters=iters, smoke=smoke,
    )
    if as_json:
        click.echo(json.dumps(summary))
        return
    click.echo(f"device_kind: {summary['device_kind']} (platform {summary['platform']}, "
               f"interpret={summary['interpret']})")
    for kernel, timings in summary["timings"].items():
        for label, secs in sorted(timings.items(), key=lambda kv: kv[1]):
            click.echo(f"  {kernel:18s} {label:32s} {secs * 1e3:9.3f} ms")
    for key, blocks in summary["entries"].items():
        click.echo(f"best {key}: {blocks}")
    if "path" in summary:
        click.echo(f"table written: {summary['path']}")
        if not os.environ.get("MODALITIES_TPU_TUNE_DIR"):
            click.echo(f"export MODALITIES_TPU_TUNE_DIR={resolved_out} to use it in training")


# ---------------------------------------------------------------------- benchmark


@main.group(name="benchmark")
def benchmark() -> None:
    """Benchmark sweep tools."""


@benchmark.command(name="prepare_sweep_configs")
@click.option("--sweep_config_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--output_dir", type=click.Path(path_type=Path), required=True)
@_exception_handling
def entry_point_prepare_sweep_configs(sweep_config_path: Path, output_dir: Path) -> None:
    from modalities_tpu.utils.benchmarking.sweep_utils import SweepGenerator

    SweepGenerator.generate_sweep_configs(sweep_config_path, output_dir)


@benchmark.command(name="list_remaining_runs")
@click.option("--sweep_dir", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--skip_oom_configs", is_flag=True, default=False)
@_exception_handling
def entry_point_list_remaining_runs(sweep_dir: Path, skip_oom_configs: bool) -> None:
    from modalities_tpu.utils.benchmarking.benchmarking_utils import get_updated_sweep_status

    status = get_updated_sweep_status(sweep_dir, skip_oom_configs=skip_oom_configs)
    click.echo(json.dumps(status, indent=2, default=str))


@benchmark.command(name="validate_recipe")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@click.option("--hbm_budget_gib", type=float, default=95.0, help="Per-chip HBM budget (v5p: 95).")
@click.option(
    "--warmstart_checkpoint_folder",
    type=str,
    default=None,
    help="Real checkpoint folder for warmstart recipes (default: a synthetic name).",
)
@click.option(
    "--compile_memory_check",
    is_flag=True,
    default=False,
    help="Also COMPILE the lowered step on the virtual mesh and report XLA's own "
    "per-device memory accounting next to the formula estimate (slower).",
)
@_exception_handling
def entry_point_validate_recipe(
    config_file_path: Path,
    hbm_budget_gib: float,
    warmstart_checkpoint_folder: Optional[str],
    compile_memory_check: bool,
) -> None:
    """Compile-only v5p readiness check: lower the recipe's full sharded train step
    over a virtual mesh of its world_size and report the per-chip HBM budget
    (BASELINE.md acceptance recipes; runs in a CPU subprocess, no TPU touched)."""
    from modalities_tpu.utils.recipe_validation import run_validation_subprocess

    report = run_validation_subprocess(
        config_file_path,
        hbm_budget_bytes=int(hbm_budget_gib * 1024**3),
        warmstart_checkpoint_folder=warmstart_checkpoint_folder,
        compile_memory_check=compile_memory_check,
    )
    click.echo(json.dumps(report, indent=2))
    if report["lowering"] != "ok" or not report["fits_budget"]:
        raise SystemExit(1)


@benchmark.command(name="summarize_results")
@click.option("--sweep_dir", type=click.Path(exists=True, path_type=Path), required=True)
@_exception_handling
def entry_point_summarize_results(sweep_dir: Path) -> None:
    """Perf grid across a sweep: peak/last tokens-per-s, MFU, final loss per run."""
    from modalities_tpu.utils.benchmarking.benchmarking_utils import summarize_sweep_results

    click.echo(json.dumps(summarize_sweep_results(sweep_dir), indent=2, default=str))


# ------------------------------------------------------------------------ profile


@main.group(name="profile")
def profile() -> None:
    """Profiling harness."""


@profile.command(name="distributed")
@click.option("--config_file_path", type=click.Path(exists=True, path_type=Path), required=True)
@_exception_handling
def entry_point_profile_distributed(config_file_path: Path) -> None:
    from modalities_tpu.utils.profilers.modalities_profiler import ModalitiesProfilerStarter

    ModalitiesProfilerStarter.run_distributed(config_file_path)


if __name__ == "__main__":
    main()
