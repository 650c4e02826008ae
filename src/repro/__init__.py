"""repro: CROFT-style distributed 3-D FFT reproduction on JAX."""
