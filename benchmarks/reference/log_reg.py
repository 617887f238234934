"""Plain reference for the encrypted logistic regression, independent of the
program: numpy, float64.

Semantics (Drynx, logistic_regression.go): the log-loss is approximated by
a polynomial of degree k in the margin w.x, so a data provider's whole
contribution is the sign-weighted power tensors of its standardised,
offset-augmented rows,

    T_j = sum_i s_j(y_i) x_i^(x)j,   s_j(y) = 2y-1 for odd j, -1 for even j,

each rounded to a whole number at `precision` before encryption. The
servers add the providers' vectors; the querier decrypts the sums and runs
gradient descent on the approximated, l2-regularised cost, keeping the
weights of the lowest cost seen. Only k <= 2 is written out here.

The rounding is part of the result: the decrypted sums are held exactly to
the float64 encoding (`decrypted_diff_max` 0, the configuration's
`exact_aggregate`). `encoded()` is one provider's vector, for
check_encode.py.
"""
import numpy as np


def _tensors(X, y, means, std_devs, k):
    Xs = (np.asarray(X, dtype=np.float64) - means) / std_devs
    Xa = np.concatenate([np.ones((Xs.shape[0], 1)), Xs], axis=1)
    y = np.asarray(y, dtype=np.float64)
    out = [((2.0 * y - 1.0)[:, None] * Xa).sum(axis=0)]
    if k >= 2:
        out.append(-(Xa.T @ Xa).reshape(-1))
    if k > 2:
        raise NotImplementedError("the reference writes out k <= 2")
    return out


def _cost_and_grad(w, T1, T2, n, lam, c):
    reg = np.concatenate([np.zeros(1, w.dtype), w[1:]])
    cost = (c[1] * (T1 @ w) + c[2] * (w @ T2 @ w)) / n - c[0] \
        + lam / (2 * n) * (reg @ reg)
    grad = (c[1] * T1 + c[2] * ((T2 + T2.T) @ w)) / n + lam / n * reg
    return cost, grad


def train(sums, lr: dict, n_features: int, n_records: int, dtype=np.float64):
    """Gradient descent as the querier runs it, every value held in `dtype`
    (float64 for the reference; the control passes bfloat16)."""
    dp1 = n_features + 1
    as_t = lambda v: np.asarray(v, dtype=np.float64).astype(dtype)
    vals = np.asarray(sums, dtype=np.float64) / lr["precision"]
    T1, T2 = as_t(vals[:dp1]), as_t(vals[dp1:dp1 + dp1 * dp1]).reshape(dp1, dp1)
    c = [as_t(x) for x in lr["coeffs"]]
    n, lam, step = as_t(n_records), as_t(lr["lambda_"]), as_t(lr["step"])
    w = np.zeros(dp1, dtype=dtype)
    best_w, best_c = w, np.inf
    for _ in range(int(lr["max_iterations"])):
        cost, grad = _cost_and_grad(w, T1, T2, n, lam, c)
        if cost < best_c:
            best_w, best_c = w, cost
        w = (w - step * grad).astype(dtype)
    cost, _ = _cost_and_grad(w, T1, T2, n, lam, c)
    return np.asarray(w if cost < best_c else best_w, dtype=np.float64)


def encoded(config: dict, data: dict, dp: int):
    """One provider's vector before rounding (float64) and as encrypted."""
    lr = config["lr"]
    X, y = data["per_dp"][dp]
    packed = np.concatenate(_tensors(
        X, y, np.asarray(data["means"]), np.asarray(data["std_devs"]),
        int(lr["k"]))) * lr["precision"]
    return packed, np.round(packed).astype(np.int64)


def expect(config: dict, data: dict) -> dict:
    total = sum(encoded(config, data, i)[1]
                for i in range(len(data["per_dp"])))
    return {"decrypted": total,
            "weights": train(total, config["lr"], int(config["n_features"]),
                             int(data["n_records"]))}


def _weights_gap(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.abs(got - want).max() / np.abs(want).max())


def compare(config: dict, expected: dict, out: dict) -> dict:
    values = np.asarray(out["values"], dtype=np.int64)
    found = np.asarray(out["found"], dtype=bool)
    want = expected["decrypted"]
    if values.shape != want.shape:
        return {"decrypted_diff_max": float("inf"),
                "dlog_missed": int(want.size), "weights_gap": float("inf")}
    return {
        "decrypted_diff_max": int(np.abs(np.where(found, values, want)
                                         - want).max()),
        "dlog_missed": int((~found).sum()),
        "weights_gap": _weights_gap(out["result"], expected["weights"]),
    }


def control(config: dict, data: dict, expected: dict, kind: str) -> dict:
    """The reference in the program's place, trained in the nearest
    precision below the float32 the configuration states."""
    if kind != "bfloat16":
        raise ValueError(f"log_reg has no control {kind!r}")
    import ml_dtypes

    w = train(expected["decrypted"], config["lr"], int(config["n_features"]),
              int(data["n_records"]), dtype=ml_dtypes.bfloat16)
    return {"values": expected["decrypted"],
            "found": np.ones(expected["decrypted"].shape, dtype=bool),
            "result": w}
