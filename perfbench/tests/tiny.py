"""A cell of ``BENCHMARK.json`` cut to a size the CPU serves in seconds:
the same files, mix and code path, at 64-wide layers and short requests."""

import copy
import json
from pathlib import Path

from perfbench import harness, model

ROOT = Path(__file__).resolve().parents[2]
WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, vocab_size=512,
              num_hidden_layers=2)
# the tiny cells' limit: their sound runs read widest gaps of 0 to 0.02 on
# the CPU, the control 0.17 (decode, seed 2**31 + 7)
LIMITS = {"logit_gap_max": {"limit": 0.08}}
SERVING = dict(num_pages=512, max_batch=4, max_seq_len=512,
               prefill_chunk_tokens=64, page_size=8, prefix_cache_entries=512)


# every mix with the configuration it is written for, in BENCHMARK.json or
# kept for a later cell (PERF.md, Open questions)
CELLS = {"qwen3-8b-l12.prefix": ("qwen3-8b-l12", "prefix"),
         "qwen3-8b-l12.decode": ("qwen3-8b-l12", "decode"),
         "qwen3-32b-l4.unshared": ("qwen3-32b-l4", "unshared")}


def tiny_cell(name: str) -> harness.Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config, mix = CELLS[name]
    bench["workloads"] = [{"name": name, "config": config, "traffic": mix,
                           "chips": 1}]
    cell = harness.load_cell(name, bench)
    cfg = dict(cell.config, **WIDTHS)
    cfg["serving"] = dict(cfg["serving"], **SERVING)
    cell.config, cell.dims = cfg, model.Dims.from_config(cfg)
    mix = copy.deepcopy(cell.mix)
    if "prefixes" in mix:
        mix["prefixes"]["tokens"] = 64
    for key, lo, hi in (("prompt", 8, 48), ("answer", 2, 12)):
        mix[key].update(min=lo, max=hi)
        if mix[key]["dist"] == "lognormal":
            mix[key]["median"] = (lo + hi) // 3
    if mix["kind"] == "open":
        mix["rate_per_s"] = 8
    else:
        mix["clients"] = 4
    mix.update(warm_seconds=0.5, warm_drain_s=2)
    cell.mix = mix
    return cell
