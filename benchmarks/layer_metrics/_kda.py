"""Shared by the ``kda_mla_moe`` core's readers: the scopes of its Kimi
Delta Attention (``models/cores/kda_mla_moe.py``): the projections and the
convolutions, the chunked scan (``ops/kda.py``'s kernels, forward and
backward), the gated norm and the output projection."""

SCOPES = ("kda_proj", "kda_scan", "kda_out")
SCAN = "kda_scan"
