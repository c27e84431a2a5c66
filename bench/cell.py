"""A cell of ``BENCHMARK.json``, loaded from its files by name.

The manifest names the cell's configuration and traffic.  The
configuration file (``configs[].file``) gives the model's published sizes,
the program's preset that runs it (``arch``) and the keys cut from the
source (``reduced``); the traffic file (``<paths[0]>/traffic/<name>.json``)
gives the clients, rows and lengths of the batches and the federated job
they feed; the limits file (``<paths[0]>/limits/<cell>.json``) gives the
limit of every number the output check compares.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import NamedTuple


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: dict        # {"end_to_end": [...], "per_layer": [...]}


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def load(manifest_path: str, workload: str) -> Cell:
    root = os.path.dirname(os.path.abspath(manifest_path))
    man = _read(manifest_path)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in "
                         f"{manifest_path}; it has {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    data = os.path.join(root, man["paths"][0])
    config = _read(os.path.join(root, conf["file"]))
    if sorted(config["reduced"]) != sorted(conf["reduced"]):
        raise SystemExit(f"bench: {conf['file']} cuts {config['reduced']}, "
                         f"the manifest says {conf['reduced']}")
    mine = lambda ms: [m for m in ms
                       if workload in m.get("workloads", [workload])]
    return Cell(workload, w["chips"], config,
                _read(os.path.join(data, "traffic", w["traffic"] + ".json")),
                _read(os.path.join(data, "limits", workload + ".json")),
                {"end_to_end": mine(man["end_to_end"]),
                 "per_layer": mine(man["per_layer"])})


@contextlib.contextmanager
def registered(config: dict):
    """The program's preset ``config["arch"]`` with the configuration's cuts
    applied, registered in ``repro.configs.ARCHS`` under the configuration's
    name for as long as the context lasts (the entry is removed after, and
    ``ARCHS`` left as found).  Every size the file gives must equal the
    preset's, except the keys listed in ``reduced``."""
    from repro import configs
    base = configs.get_config(config["arch"])
    sizes = config["sizes"]
    for k, v in sizes.items():
        if k not in config["reduced"] and getattr(base, k) != v:
            raise SystemExit(
                f"bench: the program's {config['arch']} has {k}="
                f"{getattr(base, k)!r}, the configuration states {v!r}")
    name = config["name"]
    if name == config["arch"] and not config["reduced"]:
        yield name
        return
    if name in configs.ARCHS:
        raise SystemExit(f"bench: {name!r} is already a program preset")
    configs.ARCHS[name] = dataclasses.replace(
        base, name=name, **{k: sizes[k] for k in config["reduced"]})
    try:
        yield name
    finally:
        del configs.ARCHS[name]


def experiment(config: dict, traffic: dict, arch: str):
    """The cell's :class:`repro.api.Experiment`: the traffic's job on the
    configuration's model, at the traffic's sizes."""
    from repro.api import Experiment
    spec = json.loads(json.dumps(traffic["experiment"]))
    spec["problem"] = {
        "arch": arch, "reduced": False, "num_clients": traffic["clients"],
        "per_client": traffic["per_client"], "seq_len": traffic["seq_len"],
        "client_sizes": None, "param_dtype": config["param_dtype"],
        "data_seed": 0}
    return Experiment.from_json(json.dumps(spec))
