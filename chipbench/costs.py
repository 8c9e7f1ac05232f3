"""Peaks of the chip and the work a kernel or a model step needs.

Operations and bytes are what the algorithm needs for the call, counted
from shapes and positions: padding, parked rows and recomputation are
not counted, so a roofline share also shows the work a kernel wastes.
"""
from __future__ import annotations

from dataclasses import dataclass

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Dims:
    """The widths a cost function needs (a dense, all-attention model)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2           # bf16 cache
    act_bytes: int = 2          # bf16 activations

    @classmethod
    def of(cls, c: dict) -> "Dims":
        """From a configuration file's published keys."""
        h = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=h, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // h),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return (d * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * d + 3 * d * self.d_ff)

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes


def paged_decode(d: Dims, pos: int) -> tuple[float, float]:
    """(flops, bytes) of one row of ``paged_decode_attention`` in one
    layer: the query at position ``pos`` attends keys ``0..pos``."""
    n = pos + 1
    flops = 4.0 * d.heads * d.head_dim * n
    nbytes = (2.0 * n * d.kv_heads * d.head_dim * d.kv_bytes
              + 2.0 * d.heads * d.head_dim * d.act_bytes)       # q + out
    return flops, nbytes


def paged_verify(d: Dims, pos: int, n: int) -> tuple[float, float]:
    """(flops, bytes) of one row of ``paged_verify_attention`` in one
    layer: ``n`` valid block queries at ``pos..pos+n-1`` read the ``pos``
    cached keys and the block's own keys under a causal mask."""
    keys = n * pos + n * (n + 1) / 2.0
    flops = 4.0 * d.heads * d.head_dim * keys
    nbytes = (2.0 * pos * d.kv_heads * d.head_dim * d.kv_bytes
              + 2.0 * n * d.heads * d.head_dim * d.act_bytes     # q + out
              + 2.0 * n * d.kv_heads * d.head_dim * d.act_bytes)  # block k/v
    return flops, nbytes


def token_flops(d: Dims, pos: int, logits: bool) -> float:
    """Model FLOPs of one token at ``pos``: the layers' matmuls, causal
    attention over ``pos + 1`` keys, and the head when it is needed."""
    f = d.layers * (2.0 * d.layer_params
                    + 4.0 * d.heads * d.head_dim * (pos + 1))
    return f + (2.0 * d.d_model * d.vocab if logits else 0.0)


@dataclass
class Work:
    """What the window's requests needed, summed."""
    prefill_flops: float = 0.0
    decode_flops: float = 0.0
    decode_kernel: float = 0.0      # least seconds for paged decode
    verify_kernel: float = 0.0      # least seconds for paged verify


def request_work(d: Dims, prompt: int, outputs: int, chunk: int,
                 peak: dict, w: Work) -> Work:
    """Add one request (``prompt`` tokens, ``outputs`` served) to ``w``.

    Prefill runs in ``chunk``-token blocks through the verify kernel;
    the first output token comes from the last prompt position; each
    later one from a decode step at positions ``prompt .. prompt +
    outputs - 2``.  A kernel's least time is summed call by call, each
    the larger of its operations and its bytes over the peaks."""
    pf, pb = peak["bf16_flops"], peak["hbm_bytes_per_s"]
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        f, b = paged_verify(d, start, n)
        w.verify_kernel += d.layers * max(f / pf, b / pb)
    # sum of token_flops(d, t, t == prompt - 1) over t < prompt
    w.prefill_flops += (prompt * d.layers * 2.0 * d.layer_params
                        + d.layers * 4.0 * d.heads * d.head_dim
                        * prompt * (prompt + 1) / 2.0
                        + 2.0 * d.d_model * d.vocab)
    for pos in range(prompt, prompt + outputs - 1):
        f, b = paged_decode(d, pos)
        w.decode_kernel += d.layers * max(f / pf, b / pb)
        w.decode_flops += token_flops(d, pos, True)
    return w
