"""Toy sizes of the `nemotron_h` family for the tier-1 drive of its cell
(`test_cellbench.py::test_serve_driver_end_to_end`), as a file of its own:
`tests/conftest.py` hands `FAMILY`, `LIMITS` and `shrink` to the tables of
`tests/cellbench/conftest.py`, which may not be edited."""

FAMILY = "nemotron_h"

# float32 throughout, so that the program's routing is the reference's: sound
# runs read under 1e-4 (the int8 control and both planted faults above 1e-2;
# test_hybrid_cell.py). No position is a near-tie at eps 1e-6 of the router's
# logit. A head's state is the reference's to 1e-6 of its norm; rounded to
# bfloat16 after every tick it is 1e-3 off.
LIMITS = {"served_logit_gap_p99": 1e-3, "served_logit_gap_mean": 1e-4,
          "near_tie_eps": 1e-6, "near_tie_share_max": 0.2,
          "ssm_state_err_p50": 1e-4, "ssm_state_slow_head_err_ratio": 3.0}


def shrink(cell):
    cfg, mix = cell.config, cell.traffic
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
               ssm_state_size=16, chunk_size=8, intermediate_size=32,
               moe_intermediate_size=32, moe_latent_size=24,
               moe_shared_expert_intermediate_size=48, vocab_size=256,
               pattern_kept="MEM*E", num_hidden_layers=5, experts_routed=16,
               experts_held=[0, 1, 2, 3], n_routed_experts=4,
               num_experts_per_tok=4,
               # Through two relu^2 experts' products 24 and 32 wide at
               # std 0.02 the routed experts add a millionth of what the
               # shared expert adds (a tenth at the published widths):
               # scaled up so that the toy's routed path is in play.
               routed_scaling_factor=3000.0)
    cfg["assumed"].update(param_dtype="float32", compute_dtype="float32",
                          conv_state_dtype="float32", max_seq_len=64, slots=4,
                          page_size=8)
    mix.update(prompt_len={"dist": "uniform", "lo": 8, "hi": 40},
               new_tokens={"dist": "uniform", "lo": 4, "hi": 12},
               cycle=16, clients=4, check_requests=3,
               state_check={"requests": 2, "new_tokens": 12})
