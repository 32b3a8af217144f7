"""Published per-chip peaks keyed by ``device_kind`` (Google Cloud
documentation, "TPU v5e"): bf16 FLOP/s, int8 OP/s, HBM bytes and
bytes/s.  A kind missing here is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
