"""The control of ``bench/calibrate.py`` at a size the CPU holds: it
reads a gap where the program reads none."""
from conftest import runner, tiny_serve

import calibrate


def test_serve_control_reads_wider_than_the_program(peak):
    """Float32 on the CPU reads no gap; the bfloat16 control puts other
    tokens first among 4,096 and reads one."""
    cell = tiny_serve()
    cell.config.update(vocab_size=4096, hidden_size=64,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, intermediate_size=128)
    cell.config["check"].update(sample_tokens=300)
    cell.traffic["arrivals"]["rate_per_s"] = 20.0
    seed = 2**31 + 23
    res = runner(cell).run(cell, seed=seed, seconds=2.0, trace=False,
                           peak=peak, t_start=0.0)
    program = {name: v for name, v, _ in res["checks"]}["logit_gap_mean"]
    control = calibrate.gap_stats(calibrate.control_gaps(cell, res, seed))
    assert program == 0.0 < control["mean"]
