"""Toy sizes for the families that later PRs add.

`test_cellbench.py` drives every serve cell of BENCHMARK.json end to end at
toy widths, but its `toy_cell` knows the toy widths of two families by name
and its `TOY_LIMITS` their limits. A cell of another family is a file of this
directory's own, as every addition to the benchmark is: the fixture below
gives `toy_cell` the new family's toy widths and limits, and leaves every
other cell as that module makes it.
"""

import pytest

# float32 throughout, so that the program's routing is the reference's: sound
# runs read under 1e-4 (the int8 control 0.02-0.2, a planted expert swap
# above 0.01; test_routed_cell.py). No position is a near-tie at eps 1e-6 of
# the router's logit.
TOY_LIMITS = {"exaone_moe": {"served_logit_gap_p99": 1e-3,
                             "served_logit_gap_mean": 1e-4, "near_tie_eps": 1e-6,
                             "near_tie_share_max": 0.2}}


def shrink_exaone_moe(cell):
    cfg, mix = cell.config, cell.traffic
    cfg.update(hidden_size=64, intermediate_size=96, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, vocab_size=256, sliding_window=12,
               experts_routed=16, experts_held=[0, 1, 2, 3], num_experts=4,
               num_experts_per_tok=4)
    cfg["assumed"].update(param_dtype="float32", compute_dtype="float32",
                          max_seq_len=64, slots=4, page_size=8)
    mix.update(prompt_len={"dist": "uniform", "lo": 8, "hi": 40},
               new_tokens={"dist": "uniform", "lo": 4, "hi": 12},
               cycle=16, clients=4, check_requests=3)


SHRINK = {"exaone_moe": shrink_exaone_moe}


@pytest.fixture(autouse=True)
def toy_sizes_of_later_families(request, monkeypatch):
    module = request.module
    if not hasattr(module, "toy_cell") or not hasattr(module, "TOY_LIMITS"):
        yield
        return
    for family, limits in TOY_LIMITS.items():
        monkeypatch.setitem(module.TOY_LIMITS, family, limits)
    plain = module.toy_cell

    def toy_cell(workload, **traffic_changes):
        cell = plain(workload, **traffic_changes)
        shrink = SHRINK.get(cell.config["family"])
        if shrink is not None:
            shrink(cell)
            cell.traffic.update(traffic_changes)
        return cell

    monkeypatch.setattr(module, "toy_cell", toy_cell)
    yield


def pytest_collection_modifyitems(config, items):
    """`test_cellbench.py` files every cell but the first training cell under
    its serve tests. A later training cell runs the training driver, which
    `test_train_driver_end_to_end` already drives end to end on the same
    eight virtual devices: a second such run a worker buys nothing and costs
    the suite a minute, so it is left out by name of its driver."""
    from cellbench import harness

    trained = {w["name"] for w in harness.load_benchmark()["workloads"]
               if harness.load_traffic(w["traffic"])["driver"] == "fit_window"}
    extra = [item for item in items
             if item.name.startswith("test_serve_driver_end_to_end[")
             and item.name[len("test_serve_driver_end_to_end["):-1] in trained]
    if extra:
        config.hook.pytest_deselected(items=extra)
        items[:] = [item for item in items if item not in extra]
