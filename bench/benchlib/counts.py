"""Operations and bytes the algorithms need, from logical shapes alone.

Nothing here reads the program: the counts follow the published
architecture in the configuration files, so a change to the program can
not move the yardstick.

Scan kernels (canonical top-to-bottom recurrence over G planes of H x W)::

    h[i, j] = wl h[i-1, j-1] + wc h[i-1, j] + wr h[i-1, j+1] + lam x[i, j]

Forward: 7 operations per output element (4 multiplies, 3 adds); reads x,
lam and the three taps (at G_w = G / channels-per-weight planes) once,
writes h once.  Adjoint: 6 per element (3 multiplies, 3 adds); reads the
output cotangent and the taps once, writes the f32 adjoint once.  A
fused pair shares x between its two directions.  No padding, no re-reads:
the count is the least any implementation must move.
"""

from __future__ import annotations

import dataclasses

FWD_OPS, BWD_OPS = 7, 6


@dataclasses.dataclass(frozen=True)
class ScanCall:
    kernel: str             # the kernel's name in the device trace
    g: int                  # planes (batch x channels)
    gw: int                 # tap planes
    h: int
    w: int
    stream_bytes: int       # bytes per streamed element
    out_bytes: int          # bytes per output element

    @property
    def dirs(self) -> int:
        return {"gspn_scan_fwd": 1, "gspn_pair_fwd": 2,
                "gspn_pair_bwd": 2}[self.kernel]

    def flops(self) -> float:
        per = BWD_OPS if self.kernel.endswith("_bwd") else FWD_OPS
        return float(per * self.dirs * self.g * self.h * self.w)

    def bytes(self) -> float:
        plane, wplane = self.g * self.h * self.w, self.gw * self.h * self.w
        d, s = self.dirs, self.stream_bytes
        if self.kernel.endswith("_bwd"):
            reads = d * plane * s + 3 * d * wplane * s        # dy, taps
        else:
            reads = plane * s + d * plane * s + 3 * d * wplane * s  # x, lam, taps
        return float(reads + d * plane * self.out_bytes)


# ---------------------------------------------------------------------------
# GSPN-2 vision backbone (paper Table 2)
# ---------------------------------------------------------------------------

def vision_grids(cfg: dict):
    """(dim, depth, side) per stage: the stem divides the image by 4 and
    every stage after the first halves it again."""
    side = cfg["img_size"] // 4
    for dim, depth in zip(cfg["dims"], cfg["depths"]):
        yield dim, depth, side
        side //= 2


def vision_scan_calls(cfg: dict, batch: int, train: bool) -> list[ScanCall]:
    """The scan launches of one step: per block, the vertical and the
    horizontal opposite-direction pair (forward), and their adjoints when
    training.  Streams and outputs are f32 (the configuration's policy)."""
    cp = cfg["proxy_dim"]
    gw = batch if cfg["channel_shared"] else batch * cp
    calls = []
    for _dim, depth, side in vision_grids(cfg):
        for _ in range(depth):
            for _pair in range(2):
                calls.append(ScanCall("gspn_pair_fwd", batch * cp, gw, side,
                                      side, 4, 4))
                if train:
                    calls.append(ScanCall("gspn_pair_bwd", batch * cp, gw,
                                          side, side, 4, 4))
    return calls


def vision_macs(cfg: dict) -> int:
    """Multiply-accumulates for one image's forward pass: stem, per block
    two depthwise 3x3 LPUs, the GSPN-2 projections, the four directional
    scans and the MLP, the downsampling convolutions and the head."""
    nd = 4
    cp = cfg["proxy_dim"]
    taps = 3 * nd if cfg["channel_shared"] else 3 * nd * cp
    macs = (cfg["img_size"] // 4) ** 2 * 16 * cfg["in_chans"] * cfg["dims"][0]
    dims = cfg["dims"]
    for si, (dim, depth, side) in enumerate(vision_grids(cfg)):
        n = side * side
        proj = dim * cp + dim * taps + 2 * dim * nd * cp + cp * dim
        hidden = int(dim * cfg["mlp_ratio"])
        per_block = (n * dim * 9 * 2 + n * proj + nd * n * cp * 4
                     + 2 * n * dim * hidden)
        macs += depth * per_block
        if si + 1 < len(dims):
            macs += (side // 2) ** 2 * 4 * dim * dims[si + 1]
    macs += dims[-1] * cfg["n_classes"]
    return macs


def vision_flops_per_image(cfg: dict, train: bool) -> float:
    """2 operations per multiply-accumulate; a training step is counted as
    three forward passes (forward, and the backward's two products)."""
    return 2.0 * vision_macs(cfg) * (3 if train else 1)


# ---------------------------------------------------------------------------
# GSPN language model
# ---------------------------------------------------------------------------

def lm_layer_params(cfg: dict) -> int:
    """Weights one token meets in one layer: the GSPN mixer's projections
    (down, 3 taps, row gate, lambda and u for both passes, up) and the
    SwiGLU FFN."""
    d, cp, ff = cfg["d_model"], cfg["gspn_proxy_dim"], cfg["d_ff"]
    mixer = d * cp + d * 3 + d + d * 2 * cp * 2 + cp * d
    return mixer + 3 * d * ff


def lm_flops_per_token(cfg: dict, head: bool) -> float:
    """2 N operations per token, N the weights a token meets: every
    layer, and the vocabulary head where the program computes logits."""
    n = cfg["n_layers"] * lm_layer_params(cfg)
    if head:
        n += cfg["d_model"] * cfg["vocab"]
    return 2.0 * n
