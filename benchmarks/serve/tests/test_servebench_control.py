"""The control at a size the CPU holds: the reference with int8 weights,
put in the program's place, goes through the benchmark's own check and
comes out not correct where the program comes out correct. On the CPU the
program computes in exact float32, so at this size the limits are 0. On
the chip the control is read at the cells' own sizes by ``study.py
check`` (PERF.md gives those readings)."""
import pytest

from servebench_small import run, small_config, small_traffic

from servebench import traffic  # noqa: E402

SEEDS = [3, 2**31 + 7, 2**32 + 11]
EXACT = {"mean_gap": 0.0, "gap_per_near_tie": 0.0}


@pytest.mark.parametrize("name,cell", [("granite_3_8b", "granite_3_8b.chat"),
                                       ("starcoder2_15b",
                                        "starcoder2_15b.code")])
def test_control_fails_where_program_passes(name, cell):
    c = small_config(name)
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=8)
    c["check"] = dict(c["check"], sample_served_tokens=300, max_requests=40,
                      limits=EXACT)
    t = dict(small_traffic(cell), rate_rps=10.0)
    sess = run.Session(c, SEEDS[0])
    run.warm(sess, t["prompt"]["ladder"], SEEDS[0])
    for seed in SEEDS:
        sess.set_weights(seed)
        gen = traffic.Generator(t, seed, 2.5, c["serving"]["max_len"],
                                sess.shape.vocab)
        log = run.measure(sess, gen, 2.5)
        verdicts = {}
        for control in (False, True):
            readings, limits, n = run.check(sess, log, seed, control=control)
            assert n >= 5
            verdicts[control] = run.verdict(readings, limits, n, log.compiles)
        assert verdicts == {False: True, True: False}, seed
        sess.eng.run()
