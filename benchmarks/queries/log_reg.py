"""Query of the encrypted logistic regression: the program's LRParams from
the configuration's `lr` and the pool's standardisation. A data provider
sends its power tensors of degree 1..k over the offset-augmented features."""


def query_kwargs(config: dict, data: dict) -> dict:
    from drynx_tpu.models import logreg as lr

    spec = dict(config["lr"])
    spec["coeffs"] = tuple(spec["coeffs"])
    return {"lr_params": lr.LRParams(
        n_features=int(config["n_features"]),
        n_records=int(data["n_records"]), means=data["means"],
        std_devs=data["std_devs"], **spec)}


def n_values(config: dict) -> int:
    dp1 = int(config["n_features"]) + 1
    return sum(dp1 ** j for j in range(1, int(config["lr"]["k"]) + 1))
