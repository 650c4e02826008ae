"""Pallas TPU kernels for the FFT hot spots: compiled to Mosaic on TPU,
interpreted elsewhere (``backend.resolve_interpret``).

backend         the interpret-mode resolver and VMEM block sizing

fft_matmul      four-step (Bailey) batched 1-D FFT on the MXU
spectral_scale  fused frequency-domain complex multiply-scale
transpose_pack  the ring/pairwise transposes' fused block rotation
hermitian       real-transform pack/unpack: fused two-for-one Hermitian
                split (r2c) and Hermitian extension (c2r) plane kernels
ops             jit'd complex-in/complex-out wrappers
ref             pure-jnp oracles for the test sweeps
"""

from repro.kernels.hermitian import (hermitian_extend_planes,
                                     unpack_two_for_one_planes)
from repro.kernels.ops import fft_matmul_1d, spectral_scale_op

__all__ = ["fft_matmul_1d", "hermitian_extend_planes", "spectral_scale_op",
           "unpack_two_for_one_planes"]
