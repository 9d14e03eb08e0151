"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud TPU documentation, system architecture, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. A device
that is not in the table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            f"to chipbench/peaks.py with its source") from None


def kv_bytes_per_token_per_layer(hf: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one token holds in one layer's pages."""
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    return 2 * kv_heads * head_dim * kv_dtype_bytes
