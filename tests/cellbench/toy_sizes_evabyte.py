"""Toy sizes of the `evabyte` family for the tier-1 drive of its cell
(`test_cellbench.py::test_serve_driver_end_to_end`), as a file of its own:
`tests/conftest.py` hands `FAMILY`, `LIMITS` and `shrink` of every
`toy_sizes_<family>.py` here to the tables of `tests/cellbench/conftest.py`,
which may not be edited."""

FAMILY = "evabyte"

# float32 throughout: sound runs read under 1e-4 (the int8 control and the
# planted faults above 1e-2; test_eva_cell.py).
LIMITS = {"served_logit_gap_max": 1e-3}


def shrink(cell):
    """Windows of 32 tokens and chunks (and pages) of 4: prompts of 40-100
    cross one to three window ends in their prefill, and 24 new tokens one
    more while decoding for most of them."""
    cfg, mix = cell.config, cell.traffic
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               intermediate_size=96, vocab_size=64, num_hidden_layers=2,
               window_size=32, chunk_size=4, num_pred_heads=3)
    cfg["assumed"].update(param_dtype="float32", compute_dtype="float32",
                          kv_page_dtype="float32", max_seq_len=128, slots=4,
                          page_size=4)
    mix.update(prompt_len={"dist": "uniform", "lo": 40, "hi": 100},
               new_tokens={"dist": "fixed", "value": 24},
               cycle=8, clients=4, check_requests=3)
