"""LM serving example: the spectral-mixer layer as a transform service.

The FNet-style mixer (``repro.models.spectral``) is ``Re(FFT_seq(
FFT_model(x)))`` — a 2-D FFT over (seq, d_model).  Embedded as a 3-D
c2c of shape (1, S, D) (the size-1 leading axis transforms to itself),
each user's mixing call becomes one :class:`repro.serve.TransformService`
request: concurrent users land in the same dispatch window, get stacked
into one batched FFT, and share a single plan — the same continuous
batching an LM server applies to decode steps, here at the layer level.

    PYTHONPATH=src python examples/serve_lm.py --users 4 --layers 3

Each user's served output is checked against the direct
``spectral_mixer`` call.  The legacy prefill/decode loop lives on in
``python -m repro.launch.serve --arch rwkv6-3b --smoke``.
"""

import argparse
import threading

import jax.numpy as jnp
import numpy as np

from repro.models.spectral import spectral_mixer
from repro.serve import TransformService
from repro.launch.compile_cache import use_compile_cache


def mixer_via_service(svc: TransformService, x: np.ndarray) -> np.ndarray:
    """One mixer layer for one user, served: x (S, D) real -> (S, D)."""
    spectrum = svc.transform(x[None].astype(np.complex64), problem="c2c")
    return np.real(spectrum[0]).astype(x.dtype)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=4)
    ap.add_argument("--layers", type=int, default=3,
                    help="stacked mixer layers per user")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--dmodel", type=int, default=32)
    ap.add_argument("--wisdom", default=None)
    args = ap.parse_args()
    use_compile_cache()

    rng = np.random.RandomState(0)
    prompts = [rng.randn(args.seq, args.dmodel).astype(np.float32)
               for _ in range(args.users)]
    outputs = [None] * args.users

    def user(i):
        h = prompts[i]
        for _ in range(args.layers):
            h = mixer_via_service(svc, h)
        outputs[i] = h

    with TransformService(max_batch=args.users, max_wait_ms=2.0,
                          wisdom_path=args.wisdom) as svc:
        threads = [threading.Thread(target=user, args=(i,))
                   for i in range(args.users)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = svc.stats()

    worst = 0.0
    for i in range(args.users):
        ref = np.asarray(prompts[i][None])
        for _ in range(args.layers):
            ref = np.asarray(spectral_mixer(jnp.asarray(ref)))
        worst = max(worst, float(np.max(np.abs(outputs[i] - ref[0]))))
    scale = max(float(np.max(np.abs(o))) for o in outputs)

    print(f"{args.users} users x {args.layers} mixer layers "
          f"({args.seq}x{args.dmodel}): max|served - direct| = {worst:.3e} "
          f"(output scale {scale:.1f})")
    print(f"served {stats['requests']} requests in {stats['batches']} "
          f"batches (mean batch {stats['mean_batch']:.2f}, occupancy "
          f"{stats['occupancy']:.0%})")
    print(f"plan cache: {stats['plan_cache']['stats']}")
    assert worst < 1e-2 * max(scale, 1.0), worst
    print("OK")


if __name__ == "__main__":
    main()
