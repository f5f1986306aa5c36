"""Serving launcher:
``python -m repro.launch.serve --arch <id> [--layers N | --smoke]``.

Serves seeded requests through the real-compute disaggregated engine
(prefill worker -> KV ring -> decode worker, RAPID controller on) on JAX's
default device. Without ``--smoke`` the model keeps its published widths,
and ``--layers N`` serves one chip's share of it: its first N layers and,
of a MoE model, the first of ``EXPERT_SHARDS`` expert shards of each MoE
layer; no width changes. That is how a model too large for one chip is
served on one. ``--smoke`` serves
the reduced 2-layer, width-256 config with small traffic, for the CPU.
Weights and prompts are random from ``--seed``.

Compile-only checks of the one-chip configuration (``Traffic()`` at
``CHIP_LAYERS``) live in ``tests/test_tpu_compile.py``; ``chip_smoke.py``
serves it on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, get_config
from repro.core.controller import ControllerConfig
from repro.core.goodput import GoodputSummary
from repro.serving.engine import DisaggEngine


@dataclasses.dataclass(frozen=True)
class Traffic:
    """A fixed-length request mix: every prompt has ``prompt_len`` tokens
    and asks for ``out_tokens``, so prefill compiles once."""
    requests: int = 16
    prompt_len: int = 512
    out_tokens: int = 64
    decode_slots: int = 8
    max_len: int = 1024


SMOKE_TRAFFIC = Traffic(requests=16, prompt_len=24, out_tokens=12,
                        decode_slots=4, max_len=96)

# Layers of granite_3_8b (published widths, f32 weights and caches) that one
# TPU v5e serves under Traffic() with at least 2 GB of its 16 GiB to spare:
# the compiled decode step plus the waiting KV ring comes to ~12.8 GB at 8
# layers and grows ~1.4 GB per layer. tests/test_tpu_compile.py holds the
# compiled steps to that budget.
CHIP_LAYERS = 8

# A MoE model's experts are split over this many chips (expert parallel;
# the rest replicated), so one chip holds a contiguous quarter of each MoE
# layer's experts: lfm2_24b_a2b's experts 0-15 of 64.
EXPERT_SHARDS = 4


def cut_depth(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """``cfg`` with only its first ``n_layers`` layers; widths unchanged."""
    if not cfg.n_dense_layers < n_layers <= cfg.n_layers:
        raise ValueError(f"{cfg.name} has {cfg.n_layers} layers, the first "
                         f"{cfg.n_dense_layers} dense; cannot serve "
                         f"{n_layers}")
    return dataclasses.replace(cfg, n_layers=n_layers,
                               layer_types=cfg.layer_types[:n_layers])


def cut_experts(cfg: ModelConfig, shards: int) -> ModelConfig:
    """``cfg`` holding the first of ``shards`` equal, contiguous shards of
    each MoE layer's experts; routing still runs over all of them. A model
    without experts is returned as it is."""
    if not cfg.n_experts:
        return cfg
    if cfg.n_experts % shards:
        raise ValueError(f"{cfg.name}: {cfg.n_experts} experts do not split "
                         f"into {shards} shards")
    return dataclasses.replace(cfg,
                               experts_held=(0, cfg.n_experts // shards))


def describe(cfg: ModelConfig, published_layers: int) -> str:
    """One line naming ``cfg``'s depth cut and the widths it keeps."""
    kinds = cfg.layer_kinds()
    mixers = ", ".join(f"{kinds.count(k)} {k}" for k in dict.fromkeys(kinds))
    lo, hi = cfg.expert_range
    moe = (f", experts {lo}-{hi - 1} of {cfg.n_experts} held, top-"
           f"{cfg.top_k}" if cfg.n_experts else "")
    return (f"{cfg.name}: {cfg.n_layers} of {published_layers} layers "
            f"({mixers}), d_model {cfg.d_model}, heads "
            f"{cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, d_ff "
            f"{cfg.d_ff}, vocab {cfg.vocab_size}{moe}")


@dataclasses.dataclass
class ServeRun:
    engine: DisaggEngine
    summary: GoodputSummary
    compile_s: dict          # first call of each step: compile + one run
    wall_s: float            # host seconds of the serve loop


def serve(cfg: ModelConfig, traffic: Traffic, *, seed: int = 0,
          n_prefill: int = 1, n_decode: int = 1,
          on_decode: Optional[Callable] = None) -> ServeRun:
    """Build the engine, compile both steps, then serve ``traffic``."""
    eng = DisaggEngine(cfg, n_prefill=n_prefill, n_decode=n_decode,
                       max_len=traffic.max_len,
                       decode_slots=traffic.decode_slots,
                       ctrl_cfg=ControllerConfig(), seed=seed,
                       on_decode=on_decode)
    compile_s = eng.warmup(traffic.prompt_len)
    rng = np.random.default_rng(seed)
    for _ in range(traffic.requests):
        eng.submit(rng.integers(0, cfg.vocab_size, traffic.prompt_len)
                   .astype(np.int32), traffic.out_tokens, 0.0)
    # every engine step ends in block_until_ready, so the loop's host
    # time covers the device work it enqueued
    t0 = time.perf_counter()
    summary = eng.run()
    wall = time.perf_counter() - t0
    return ServeRun(eng, summary, compile_s, wall)


def report(run: ServeRun) -> list:
    """Lines describing a serve run. The summary's times run on the
    engine's logical clock, scaled by a modelled power curve: they are not
    device times."""
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    s = run.summary
    return [
        f"[serve] device: {dev.platform} {dev.device_kind} "
        f"x{len(jax.devices())}",
        f"[serve] compile s (first call): prefill "
        f"{run.compile_s['prefill']:.3f}  decode {run.compile_s['decode']:.3f}",
        f"[serve] serve loop wall s: {run.wall_s:.3f}",
        f"[serve] finished {s.n_finished}/{s.n_total}",
        "[serve] peak_bytes_in_use: "
        + (str(peak) if peak is not None else "not reported by this backend"),
        f"[serve] logical (modelled) SLO summary: {s.row()}",
    ]


def main():
    from repro.launch.compile_cache import use_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--layers", type=int, default=None,
                    help="serve the first N layers at published widths "
                         "(default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config and traffic, for the CPU")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-workers", type=int, default=1)
    ap.add_argument("--decode-workers", type=int, default=1)
    args = ap.parse_args()
    if args.smoke and args.layers is not None:
        ap.error("--smoke serves the reduced config; it takes no --layers")
    print(f"[serve] compile cache: {use_compile_cache()}")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg, traffic = cfg.reduced(), SMOKE_TRAFFIC
    else:
        full = cfg.n_layers
        if args.layers is not None:
            cfg = cut_experts(cut_depth(cfg, args.layers), EXPERT_SHARDS)
        traffic = Traffic()
        print(f"[serve] {describe(cfg, full)}")
    if args.requests is not None:
        traffic = dataclasses.replace(traffic, requests=args.requests)
    run = serve(cfg, traffic, seed=args.seed,
                n_prefill=args.prefill_workers, n_decode=args.decode_workers)
    print(f"[serve] {cfg.name}: {traffic}")
    print("\n".join(report(run)))


if __name__ == "__main__":
    main()
