"""Tiny configurations for the benchmark's CPU tests."""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from chipbench import spec as S  # noqa: E402

# tied vocabulary, no bias, GQA 2:1 (the phi4-mini shape, scaled down)
TIED = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 1000}
# QKV bias, untied and unaligned vocabulary, GQA 5:1 (the qwen2.5 shape)
BIASED = {"hidden_size": 160, "intermediate_size": 256,
          "num_hidden_layers": 2, "num_attention_heads": 10,
          "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 1200}


def _shrink(conf: dict, sizes: dict, dtype: str = "bfloat16",
            max_batch: int = 4, max_context: int = 128) -> dict:
    conf = copy.deepcopy(conf)
    conf["model"].update(sizes)
    conf["serving"].update(dtype=dtype, max_batch=max_batch,
                           max_context=max_context)
    return conf


def tiny_config(name: str, sizes: dict, **serving) -> dict:
    return _shrink(S.load_json(S.BENCH_DIR / "configs" / f"{name}.json"),
                   sizes, **serving)


def tiny_cell(workload: str, sizes: dict | None = None,
              traffic: str | None = None, bench: dict | None = None,
              base=S.BENCH_DIR, **mix) -> S.Cell:
    """A cell of BENCHMARK.json (or of ``bench``, with its files under
    ``base``) at a size the CPU runs in seconds; ``traffic`` puts another
    mix of ``chipbench/traffic`` in its place."""
    cell = S.resolve(workload, bench, base)
    cell.config = _shrink(cell.config, sizes or TIED)
    cell.spec = cell.arch.model_spec(cell.config)
    t = copy.deepcopy(S.load_json(S.BENCH_DIR / "traffic" / f"{traffic}.json")
                      if traffic else cell.traffic)
    if t["kind"] == "open_loop":
        t["prompt_tokens"] = {"dist": "exponential", "mean": 20, "max": 64,
                              "round_up_to": 16}
        cell.data = dict(cell.data, rate_per_s=8.0)
    else:
        t["prompt_tokens"] = {"dist": "choice", "values": [32, 48, 64, 80]}
        t.update(outstanding=8, ramp_s=0.5)
    t["output_tokens"] = {"dist": "exponential", "mean": 12, "min": 4,
                          "max": 40}
    t.update(mix)
    cell.traffic = t
    # tiny widths read gaps of 0.001-0.02 (bf16 against float32)
    cell.data = dict(cell.data, widest_gap_limit=0.05)
    return cell


@pytest.fixture
def tied_spec():
    return S.model_spec(tiny_config("phi4-mini-3.8b", TIED, dtype="float32"))


@pytest.fixture
def biased_spec():
    return S.model_spec(tiny_config("qwen2.5-14b-pp4", BIASED,
                                    dtype="float32"))
