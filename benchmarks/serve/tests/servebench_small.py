"""Small configurations and traffic for the CPU tests: the cells' own
files, shrunk in width, depth, vocabulary and length."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from servebench import run, spec  # noqa: E402

run.ensure_program_importable()


def small_config(name: str, slots: int = 4, max_len: int = 96) -> dict:
    c = spec.config(name)
    c.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=256)
    if "attention_multiplier" in c:
        c["attention_multiplier"] = 1 / 32 ** 0.5
    c["serving"] = dict(c["serving"], decode_slots=slots, max_len=max_len)
    c["check"] = dict(c["check"], sample_served_tokens=1000)
    return c


def small_traffic(name: str) -> dict:
    t = spec.traffic(name)
    t["prompt"] = dict(t["prompt"], median=16, ladder=[8, 16, 24])
    t["output"] = dict(t["output"], median=10, min=6, max=20)
    if "rate_rps" in t:
        t["rate_rps"] = 6.0
    if "backlog" in t:
        t["backlog"] = 12
    return t
