"""``ldoc_latent_decode_roofline`` (the latent kernel at the decode shape:
the live pages' ``c ‖ k_r`` over the peak HBM rate against the kernel's
time a call) where it moves this cell's own end-to-end metric: ONE latent
layer in seven (models/ling_hybrid.py), 128 rows, the same 640-lane rows.
A call is one layer of one step whatever the number of such layers."""
from .ldoc_latent_decode_roofline import read  # noqa: F401
