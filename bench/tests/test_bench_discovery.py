"""A cell added as data alone is picked up by name: a new traffic mix
file, a new cell file and a new BENCHMARK.json entry, and no code
edit, give a correct run of the new cell; a mix or a node model that a run
cannot honour is refused."""
import json

import pytest

from benchtest import CPU, add_cell


def test_new_cell_file_is_picked_up(tiny, runner):
    d = tiny.dir
    mix = json.loads((d / "traffic" / "ycsbA.w16r16.zipf099.json")
                     .read_text())
    mix.update(write_rate=4.0, read_rate=28.0)
    (d / "traffic" / "ycsbB.w4r28.zipf099.json").write_text(json.dumps(mix))
    cell = json.loads((d / "cells" / "paper4r.managed.json").read_text())
    cell.update(traffic="ycsbB.w4r28.zipf099", why="test")
    add_cell(tiny.root, "paper4r.ycsbB", cell, like="paper4r.managed")

    out = runner.run_cell(tiny, "paper4r.ycsbB", 77, 0.01, False,
                          device=CPU)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"cluster_ticks_per_s", "setup_s"}


@pytest.mark.parametrize("mix,key,value", [
    ("ycsbA.w16r16.zipf099", "market", "trace"),
    ("kv.ycsbA.zipf099", "clients", 4)])
def test_a_mix_the_run_cannot_honour_is_refused(tiny, mix, key, value):
    from traffic import generator
    m = dict(tiny.traffic(mix), **{key: value})
    with pytest.raises(ValueError, match=key):
        generator.require_supported(m)


@pytest.mark.parametrize("key,value", [("work_capacity", 4),
                                       ("ticks_per_hour", 60.0),
                                       ("bid_over_mean", 2.0)])
def test_a_node_model_the_program_does_not_run_is_refused(tiny, key,
                                                          value):
    import fleetcheck
    cfg = tiny.config("bwraft-paper-4region")
    cfg["node_model"][key] = value
    with pytest.raises(ValueError, match="node model"):
        fleetcheck.build_fleet(cfg, tiny.traffic("ycsbA.w16r16.zipf099"),
                               5, 1, manage=True)
