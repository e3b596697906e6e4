"""Operations and bytes the algorithm needs, from the configuration's
shapes: what a round's model FLOPs and the update kernel's roofline are
measured against, whatever implements them."""
from __future__ import annotations


def _conv_macs(h: int, w: int, k: int, cin: int, cout: int) -> int:
    return h * w * k * k * cin * cout


def forward_flops(model: dict) -> int:
    """FLOPs of one sample's forward pass: convolutions and the head, as
    2 x multiply-accumulates (norms, activations and the pool are not
    counted).  SAME padding; stride 2 halves each side, rounding up."""
    s, chans = model["image_size"], model["channels"]
    macs = _conv_macs(s, s, 3, model["in_channels"], chans[0])
    cin = chans[0]
    for i, cout in enumerate(chans):
        if i:
            s = -(-s // 2)
        macs += _conv_macs(s, s, 3, cin, cout) + _conv_macs(s, s, 3, cout, cout)
        if cin != cout:
            macs += _conv_macs(s, s, 1, cin, cout)
        cin = cout
    macs += cin * model["n_classes"]
    return 2 * macs


def param_count(model: dict) -> int:
    """Parameters of the ResNet (convs, GroupNorm scale and bias, head)."""
    chans = model["channels"]
    n = 9 * model["in_channels"] * chans[0] + 2 * chans[0]
    cin = chans[0]
    for cout in chans:
        n += 9 * cin * cout + 9 * cout * cout + 4 * cout
        if cin != cout:
            n += cin * cout
        cin = cout
    return n + cin * model["n_classes"] + model["n_classes"]


def round_model_flops(model: dict, clients: int, local_iters: int, batch: int,
                      eval_samples: int) -> int:
    """A round's model FLOPs: local SGD at 3x forward (forward plus a
    backward of twice its cost) and the eval's forward over the real test
    samples only, so padding does not count."""
    fwd = forward_flops(model)
    return 3 * fwd * clients * local_iters * batch + fwd * eval_samples


def update_work(clients: int, n_params: int):
    """(FLOPs, bytes) of one pFedSOP round-start update over ``clients``
    f32 vectors of ``n_params``: read x and d_i per client and the shared
    global update once, write x.  Three dot products (6 FLOPs an element),
    the blend and the step (5)."""
    return 11 * clients * n_params, 4 * (3 * clients * n_params + n_params)
