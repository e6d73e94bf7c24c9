"""latent_proj_device_ms (ms): device time per train step under `q_latent`,
`kv_latent` and `rope` of the latent layers' attention halves: the four
latent products (two down, two up), the two latents' norms, the cut of the
up-projections into heads and into their unrotated, rotated and value parts
(the layout around the kernel) and RoPE, forward and backward, summed over
the layers (yardstick/latent_scope_reduce.py). What the latent costs beside
its kernel and its output projection."""

from yardstick import latent_scope_reduce


def read(run):
    ms = latent_scope_reduce.per_step_ms(run)
    return None if ms is None else \
        ms["q_latent"] + ms["kv_latent"] + ms["rope"]
