"""Fixtures for the benchmark harness's tests: the checkout root on the
path, and cells cut to a size the CPU runs in seconds."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# widths of the program's reduced configurations (repro.configs)
TINY_LM = dict(hidden_size=120, num_attention_heads=3, num_key_value_heads=1,
               intermediate_size=320, vocab_size=512, num_hidden_layers=4)


def tiny(cell):
    """``cell`` (bench.common.Cell) cut to the program's reduced model and
    a few short rows, on XLA's kernels (CPU)."""
    cell = copy.deepcopy(cell)
    c = cell.config
    c["path"]["kernel_backend"] = "xla"
    if c["family"] == "lm":
        c.update(TINY_LM)
        c["program"] = {"arch": "smollm-360m-reduced", "replace": {}}
        if cell.traffic["kind"] == "lm_batches":
            cell.traffic.update(batch=2, seq=64, ring=4)
        else:
            c["serve"].update(max_batch=4, max_len=64, block_size=16,
                              prefill_chunk_tokens=16)
            cell.traffic.update(
                requests_per_call=6, check_requests=3,
                prompt={"median": 16, "sigma": 0.5, "min": 8, "max": 40},
                output={"median": 6, "sigma": 0.3, "min": 4, "max": 12})
    else:
        c["vision_cfg"].update(image_size=32, patch_size=8, width=96,
                               head_width=32, layers=3, mlp_ratio=2)
        c["text_cfg"].update(vocab_size=256, context_length=16, width=64,
                             heads=2, layers=2, mlp_ratio=2)
        c["embed_dim"] = 64
        c["program"] = {"arch": "clip-vit-huge-reduced", "replace": {}}
        cell.traffic.update(batch=8, ring=4)
    return cell


SERVE = "smollm-360m.serve-offline"


def serve_cell():
    """The serving cell the harness is built for but BENCHMARK.json does
    not yet hold (PERF.md, Open questions): the bf16 configuration's paged
    deployment under the ``offline-16`` mix."""
    import json
    from bench import common
    read = lambda p: json.loads((common.BENCH / p).read_text())
    per_layer = [{"name": n, "unit": "%"} for n in
                 ("mfu.serve", "paged_attn_roofline", "device_idle.serve")]
    return common.Cell(SERVE, 1, read("configs/smollm-360m-bf16.json"),
                       read("traffic/offline-16.json"), [], per_layer)


@pytest.fixture
def tiny_cell():
    from bench import common
    return lambda name: tiny(serve_cell() if name == SERVE
                             else common.load_cell(name))
