"""What the algorithm needs, from the configuration's shapes alone: the
operations and bytes of a decode step, a prefill chunk and a whole
request, and the chip's published peaks they are divided by.

The program may change how it computes; these counts may not follow it.
Weights are read once a step (int8 matrices, f32 per-channel scales), the
KV cache is read as far as it is attended (bf16), new rows are written."""

from __future__ import annotations

from dataclasses import dataclass


class UnknownDeviceKind(LookupError):
    pass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # per chip, per second
    int8_ops: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


# Keyed by jax's `device_kind`.  Copied from the program's
# `device_telemetry.DEVICE_PEAKS` except int8: the Cloud page says 393
# TOP/s (the program's table says 394); nothing here divides by it yet,
# since weight-only int8 is dequantised and multiplied in bf16.
PEAKS = {
    "TPU v5 lite": Peaks(
        197e12, 393e12, 819e9, 16 * 2**30,
        "Google Cloud TPU documentation, 'TPU v5e' system architecture",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no peaks known for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add a row with its source"
        ) from None


@dataclass(frozen=True)
class Shapes:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    inter: int
    vocab: int

    @classmethod
    def of(cls, model: dict) -> "Shapes":
        h, nh = int(model["hidden_size"]), int(model["num_attention_heads"])
        return cls(
            int(model["num_hidden_layers"]), h, nh,
            int(model["num_key_value_heads"]), h // nh,
            int(model["intermediate_size"]), int(model["vocab_size"]),
        )

    @property
    def layer_matmul_params(self) -> int:
        h, hd = self.hidden, self.head_dim
        return self.layers * (
            h * self.heads * hd + 2 * h * self.kv_heads * hd
            + self.heads * hd * h + 3 * h * self.inter
        )

    @property
    def head_params(self) -> int:
        return self.hidden * self.vocab

    @property
    def matmul_params(self) -> int:
        """Same quantity as the program's `llama.matmul_param_count`."""
        return self.layer_matmul_params + self.head_params

    @property
    def layer_out_channels(self) -> int:
        hd = self.head_dim
        return self.layers * (
            self.heads * hd + 2 * self.kv_heads * hd + self.hidden
            + 2 * self.inter + self.hidden
        )

    @property
    def kv_bytes_per_position(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * 2  # K and V, bf16

    def weight_bytes(self, with_head: bool = True) -> int:
        """int8 matrices + their f32 scales, as a step streams them."""
        b = self.layer_matmul_params + 4 * self.layer_out_channels
        if with_head:
            b += self.head_params + 4 * self.vocab
        return b

    def attn_flops(self, keys_total: float) -> float:
        """QK^T and PV over `keys_total` attended key positions, summed
        over the querying positions."""
        return 4.0 * self.layers * self.heads * self.head_dim * keys_total

    def decode_step(self, batch: float, ctx_sum: float) -> tuple[float, float]:
        """(flops, bytes) of one decode step over `batch` sequences whose
        attended lengths sum to `ctx_sum`."""
        flops = 2.0 * self.matmul_params * batch + self.attn_flops(ctx_sum)
        nbytes = (
            self.weight_bytes()
            + self.kv_bytes_per_position * (ctx_sum + batch)
            + 2.0 * self.hidden * batch  # embedding rows
        )
        return flops, nbytes

    def prefill_chunk(self, chunk: float, offset: float) -> tuple[float, float]:
        """(flops, bytes) of one prompt chunk of `chunk` tokens after
        `offset` cached ones.  The head is needed once a request, at the
        last position; it is counted in `prompt_flops`, not here."""
        keys = chunk * offset + chunk * (chunk + 1) / 2.0
        flops = 2.0 * self.layer_matmul_params * chunk + self.attn_flops(keys)
        nbytes = (
            self.weight_bytes(with_head=False)
            + self.kv_bytes_per_position * (offset + chunk)
            + 2.0 * self.hidden * chunk
        )
        return flops, nbytes

    def prompt_flops(self, prompt_len: int) -> float:
        """Model flops to turn a prompt into its first token."""
        keys = prompt_len * (prompt_len + 1) / 2.0
        return (2.0 * self.layer_matmul_params * prompt_len
                + 2.0 * self.head_params + self.attn_flops(keys))

    def token_flops(self, ctx: int) -> float:
        """Model flops of one generated token that attends `ctx` positions."""
        return 2.0 * self.matmul_params + self.attn_flops(ctx)


def roofline_ms(flops: float, nbytes: float, peaks: Peaks) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_f, t_b = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s
    return 1e3 * max(t_f, t_b), ("flops" if t_f >= t_b else "bytes")
