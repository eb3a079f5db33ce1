"""Cells at a size a CPU test run holds: the same runners, references and
checks as the chip cells, with the program's own reduced configurations and
small traffic.  Used by the benchmark's tests; the chip runs never use it.
"""
from __future__ import annotations

import copy
import time
from typing import Any, Callable, Dict, Optional

import bench_harness as H

SMALL_MAMBA2 = {"d_model": 128, "n_layer": 4, "vocab_size": 512, "d_state": 32,
                "headdim": 32, "chunk_size": 32}
SMALL_STABLELM = {"hidden_size": 128, "num_hidden_layers": 4,
                  "num_attention_heads": 4, "num_key_value_heads": 4,
                  "intermediate_size": 256, "vocab_size": 512}


def small_config(name: str) -> Dict[str, Any]:
    spec = H.benchmark_spec()
    cfg = copy.deepcopy(H.load_config(spec, name))
    cfg.update(SMALL_MAMBA2 if name.startswith("mamba2") else SMALL_STABLELM)
    cfg["program"]["reduced"] = True
    return cfg


def small_traffic(name: str) -> Dict[str, Any]:
    tr = copy.deepcopy(H.load_traffic(name))
    if tr["kind"] == "train":
        tr.update(global_batch=4, seq_len=64, ckpt_every=4, log_every=4,
                  nominal_step_s=0.25,
                  run_name="small", ref_rows_per_block=2)
        tr["corpus"].update(n_samples=64, sample_tokens=65, shard_size=16)
    else:
        tr.update(batch=3, max_len=64, prompt_buckets=[8, 16], max_new_tokens=6,
                  nominal_batch_s=0.25, warmup_new_tokens=2,
                  check_min_tokens=60)
    return tr


class FakeDevice:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return None


def run_small(workload: str, seed: int, *, seconds: float = 1.0,
              plant: Optional[Callable[..., Any]] = None,
              limits: Optional[Dict[str, Any]] = None,
              control: bool = False) -> H.RunResult:
    """One run of `workload` at the small size, without the chip check."""
    spec = H.benchmark_spec()
    cell = H.find_cell(spec, workload)
    traffic = small_traffic(cell["traffic"])
    args = H.RunArgs(
        workload=workload, seed=seed, seconds=seconds, trace=False,
        config=small_config(cell["config"]), traffic=traffic,
        limits=limits or H.load_checks(workload),
        reference=H.load_reference(spec, cell["config"]),
        devices=[FakeDevice()], t_start=time.perf_counter(), plant=plant,
        control=control)
    return H.load_runner(traffic["kind"]).run(args)
