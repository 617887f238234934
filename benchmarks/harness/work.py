"""Bytes the G1 work of one survey has to move, from the cell's shapes, and
the table of the chip's peaks.

Counted from the algorithm's inputs and outputs, so the count is the same
whatever implements the kernels: a point is 3 x 16 uint32 limbs (192 B), a
ciphertext two points (384 B), a scalar 16 limbs (64 B), a plaintext 8 B.
Intermediate points, window tables and padding are not counted: a kernel
that moves them too reads a smaller share. Only the scalar multiplications
are counted (encrypt, key switch, decrypt); the aggregation's point
additions run as XLA fusions today, outside the kernels this share times.

  encrypt      in: n_dps*V plaintexts and scalars    out: n_dps*V ciphertexts
  key switch   in: V ciphertexts, n_cns*V scalars    out: V ciphertexts and
                                                          2*n_cns*V points
  decrypt      in: V ciphertexts                     out: V points
"""
POINT, CIPHERTEXT, SCALAR, PLAIN = 192, 384, 64, 8

# Published peaks of one chip, keyed by jax's device_kind. Source: Google
# Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30},
}


def g1_bytes_per_survey(config: dict, v: int) -> int:
    """`v`: length of the encrypted vector one data provider sends
    (`queries/<name>.py` `n_values`)."""
    n_dps, n_cns = config["roster"]["n_dps"], config["roster"]["n_cns"]
    encrypt = n_dps * v * (PLAIN + SCALAR + CIPHERTEXT)
    key_switch = v * CIPHERTEXT + n_cns * v * SCALAR + v * CIPHERTEXT \
        + 2 * n_cns * v * POINT
    decrypt = v * CIPHERTEXT + v * POINT
    return encrypt + key_switch + decrypt


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]
