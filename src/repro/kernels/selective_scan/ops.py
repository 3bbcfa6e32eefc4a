"""Entry point of the selective-scan kernel at any model width."""
from __future__ import annotations

from repro.kernels.selective_scan.selective_scan import selective_scan


def selective_scan_op(x, dt, a, b_ssm, c_ssm, d_skip, h0=None, *,
                      block_d: int = 512, interpret: bool = False):
    """The Pallas scan with the widest power-of-two block of at most
    `block_d` that divides d_inner (hymba's 3200 takes 128)."""
    di = x.shape[2]
    while di % block_d:
        block_d //= 2
    return selective_scan(x, dt, a, b_ssm, c_ssm, d_skip, h0,
                          block_d=max(block_d, 1), interpret=interpret)
