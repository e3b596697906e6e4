"""Operations and bytes the algorithm needs: what a round's model FLOPs
and the update kernel's roofline are measured against, whatever
implements them.  A model family gives one sample's forward FLOPs and the
parameter count (``families/<family>.py``); the round's arithmetic is
here."""
from __future__ import annotations


def round_model_flops(forward_flops: int, clients: int, local_iters: int, batch: int,
                      eval_samples: int) -> int:
    """A round's model FLOPs, from one sample's forward FLOPs: local SGD
    at 3x forward (forward plus a backward of twice its cost) and the
    eval's forward over the real test samples only, so padding does not
    count."""
    return (3 * forward_flops * clients * local_iters * batch
            + forward_flops * eval_samples)


def update_work(clients: int, n_params: int):
    """(FLOPs, bytes) of one pFedSOP round-start update over ``clients``
    f32 vectors of ``n_params``: read x and d_i per client and the shared
    global update once, write x.  Three dot products (6 FLOPs an element),
    the blend and the step (5)."""
    return 11 * clients * n_params, 4 * (3 * clients * n_params + n_params)
