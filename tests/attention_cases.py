"""Attention shapes shared by the kernel and gradient parity suites."""

# (B, S, T, H, Hkv, D, causal, window) at the tiles ops.attention picks
TILED = [
    (1, 512, 512, 2, 2, 64, False, 0),      # one 512² tile, mask-free forward
    (1, 1024, 1024, 2, 2, 64, False, 0),    # the ESM-2 cell's 1024² tiles
    (1, 200, 200, 2, 2, 64, False, 0),      # unaligned: 128² tiles over 256
    (1, 1500, 1500, 2, 2, 64, False, 0),    # 1,500 frames: 512² over 1,536
    (1, 256, 256, 4, 2, 128, True, 0),      # D = 128 GQA, causal
    (1, 256, 256, 6, 2, 128, True, 96),     # ... and windowed
    (1, 200, 1500, 2, 2, 64, False, 0),     # cross-attention, S != T
    (1, 37, 37, 4, 2, 128, True, 0),        # a short prompt: one 37-row tile
    (1, 37, 1500, 2, 2, 64, False, 0),      # ... cross-attending 1,500 frames
    (1, 256, 256, 3, 3, 64, False, 0),      # odd head count: head-major
]
