"""Toy sizes of the `sdar` family for the tier-1 drive of its cell
(`test_cellbench.py::test_serve_driver_end_to_end`), as a file of its own:
`tests/conftest.py` hands `FAMILY`, `LIMITS` and `shrink` of every
`toy_sizes_<family>.py` to the tables of `tests/cellbench/conftest.py`."""

FAMILY = "sdar"

# float32 throughout, so that the program's routing is the reference's:
# sound runs read 0 on the CPU; over 96 new tokens the int8 control reads a
# p99 of 3.6e-3 and a mean of 5e-5, a causal block 0.063 and 3.5e-3, a
# block closed without its clean pass 0.22 and 0.028 (test_sdar_cell.py).
# No position is a near-tie at eps 1e-6.
LIMITS = {"served_logit_gap_p99": 1e-4, "served_logit_gap_mean": 1e-5,
          "near_tie_eps": 1e-6, "near_tie_share_max": 0.2,
          "rank_eps": 1e-5, "rank_flip_share_max": 0.05}


def shrink(cell):
    """Every width cut, the structure kept: blocks of 4, 16 experts of
    which 2 are chosen, two layers; prompts of 8-28 tokens (most end
    inside a block) and 32 new ones, denoised in 4 or 2 steps a block."""
    cfg, mix = cell.config, cell.traffic
    cfg.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
               num_experts=16, num_experts_per_tok=2, vocab_size=256,
               num_hidden_layers=2)
    cfg["assumed"].update(param_dtype="float32", compute_dtype="float32",
                          kv_page_dtype="float32", max_seq_len=64, slots=4,
                          page_size=4, mask_token=255)
    mix.update(prompt_len={"dist": "uniform", "lo": 8, "hi": 28},
               new_tokens={"dist": "fixed", "value": 32},
               token_ids_below=255, cycle=8, clients=4, check_requests=3)
