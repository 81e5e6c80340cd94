"""What one step's calls of the blockwise attention cost by count: the
operations of the key tiles they WALK and the bytes they have to move,
from the configuration's ``model`` block alone (nothing of the program:
the tile walk is counted here by its definition).

A call works on tiles of ``BLOCK`` queries (of every query head that
shares a key/value head) by ``BLOCK`` keys. A tile is walked if one of
its (query, key) pairs is visible: the key at or behind the query and,
in a layer with a window, less than ``sliding_window`` behind it. A
walked tile is computed whole, masked or not, as the MXU computes it: two
products forward (scores, values), five backward (the scores again, dV,
dP, dQ, dK), each ``2 x queries x keys x head size`` operations. So this
counts tiles walked, not pairs needed: a kernel that masked the band
instead of skipping it would walk, and be charged, the causal triangle.
"""

BLOCK = 512  # dptpu.ops.attention.DEFAULT_BLOCK, the cell's tile
PRODUCTS_A_TILE = 2 + 5  # forward, backward


def windows(model: dict) -> list:
    """The window of each attention call of one forward pass, None for a
    layer that shows every key behind the query."""
    return [model["sliding_window"] if kind == "sliding_attention" else None
            for kind in model["layer_types"]]


def walked_tiles(length: int, window=None, block: int = BLOCK) -> int:
    """Tiles ``(i, j)``, ``j <= i``, that hold a visible pair: the
    nearest pair of two tiles lies ``(i - j - 1) x block + 1`` apart."""
    block = min(block, length)
    n = -(-length // block)
    if window is None or window >= length:
        return n * (n + 1) // 2
    return sum(1 for i in range(n) for j in range(i + 1)
               if (i - j - 1) * block + 1 < window)


def call_flops(model: dict, window) -> float:
    """Operations of one call on one row, forward and backward once."""
    block = min(BLOCK, model["sequence_length"])
    tile = 2.0 * block * block * model["head_dim"]
    return walked_tiles(model["sequence_length"], window) \
        * model["num_attention_heads"] * tile * PRODUCTS_A_TILE


def call_bytes(model: dict, itemsize: int = 2) -> float:
    """Bytes one call on one row has to move, each array once: forward
    reads q, k, v and writes the output and one float32 log-sum-exp a
    query; backward reads q, k, v, the output's cotangent, the
    log-sum-exp and the float32 row sums of dO x O, and writes dq, dk,
    dv. A window leaves them as they are: every query and key is in
    some walked tile."""
    d, length = model["head_dim"], model["sequence_length"]
    queries = length * model["num_attention_heads"]
    keys = length * model["num_key_value_heads"]
    forward = itemsize * d * (2 * queries + 2 * keys) + 4 * queries
    backward = itemsize * d * (3 * queries + 4 * keys) + 2 * 4 * queries
    return float(forward + backward)


def step_seconds(model: dict, rows: int, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time a chip of ``peaks`` needs for
    one step's calls on ``rows`` rows, each call the larger of its
    operations over the bf16 peak and its bytes over the HBM bandwidth,
    and which of the two bounds the sum."""
    flops = [call_flops(model, w) * rows / peaks["bf16_flops_per_s"]
             for w in windows(model)]
    moved = call_bytes(model) * rows / peaks["hbm_bytes_per_s"]
    least = sum(max(f, moved) for f in flops)
    return least, "flops" if sum(flops) >= moved * len(flops) else "bytes"
