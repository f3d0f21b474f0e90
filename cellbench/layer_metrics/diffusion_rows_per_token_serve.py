"""Query rows a block-diffusion model's ticks ran for each token they
committed, from the program's counters over the window
(`diffusion_rows_computed` / `diffusion_tokens_committed`): B rows a live slot
and tick, masked, committed and clean-pass rows alike. 1 would be a decoder
that yields a token a row; what lies above it is the work diffusion costs a
token. A program without the counters: nothing to read."""


def read(observed):
    c = observed.get("counters", {})
    rows, tokens = c.get("diffusion_rows_computed"), c.get(
        "diffusion_tokens_committed")
    return rows / tokens if rows and tokens else None
