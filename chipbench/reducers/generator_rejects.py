"""``overloaded`` lines the gateway answered the generator with."""


def reduce(run: dict, args: dict):
    return float(run["gen"]["rejected"])
