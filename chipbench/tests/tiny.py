"""Cells at sizes a CPU test can hold, on the same path as the chip's."""
from chipbench import harness

WINDOW_S = 1.0


def spec(workload: str) -> harness.CellSpec:
    """The cell of ``BENCHMARK.json``, its configuration and mix cut down:
    widths, depth and vocabulary for qwen2-0.5b, stages and image size for
    resnet18, batch and lengths for both."""
    s = harness.CellSpec(workload)
    if s.cell["config"] == "qwen2-0.5b":
        s.cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                     num_key_value_heads=2, num_hidden_layers=2, vocab_size=1000)
        s.mix.update(batch=2, max_len=48, check_requests=2, trace_seconds=WINDOW_S,
                     prompt=dict(median=16, sigma=0.5, min=8, max=32, round="pow2", cycle=4),
                     new_tokens=dict(median=4, sigma=0.8, min=2, max=8))
    else:
        s.cfg.update(stage_channels=[8, 16], blocks_per_stage=[1, 1], num_classes=10)
        s.cfg["stem"].update(input_hw=16, channels=8)
        s.mix.update(batch=2, input_batches=2, check_calls=2, check_images=2,
                     trace_seconds=WINDOW_S)
    return s


def run(workload: str, seed: int, trace: bool = False, patch=None, spec_=None,
        backend: str = "interpret", control_bits: int = 0):
    import time

    return harness.run_cell(workload, seed, WINDOW_S, trace, t_start=time.perf_counter(),
                            require_tpu=False, backend=backend, spec=spec_ or spec(workload),
                            patch=patch, control_bits=control_bits)
