"""Query of a `sum` with differential privacy: the range of one row and
the program's DiffPParams from the configuration's `diffp`. A data provider
sends one encrypted value, the sum of its rows."""


def query_kwargs(config: dict, data: dict) -> dict:
    from drynx_tpu.parallel import dro
    from drynx_tpu.service.query import DiffPParams

    if int(config["diffp"]["noise_list_size"]) > dro.CHUNK \
            and not hasattr(dro, "PROGRAMS"):
        # a program from before the slab programs encrypts the whole list
        # in ONE dispatch, which at the cell's size cannot compile for the
        # chip: say so now, in set-up, before minutes of tracing lead there
        raise RuntimeError(
            "this program cannot run the deployment: parallel/dro.py has "
            "no slab programs (dro.PROGRAMS) and would encrypt the "
            f"{config['diffp']['noise_list_size']}-value noise list in one "
            "dispatch")
    lo = int(config["query_min"])
    d = config["diffp"]
    return {"query_min": lo, "query_max": lo + int(config["n_buckets"]) - 1,
            "diffp": DiffPParams(
                noise_list_size=int(d["noise_list_size"]),
                lap_mean=float(d["lap_mean"]),
                lap_scale=float(d["lap_scale"]), quanta=float(d["quanta"]),
                scale=float(d["scale"]), limit=float(d["limit"]))}


def n_values(config: dict) -> int:
    return 1
