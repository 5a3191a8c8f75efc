"""Build the system under test from a configuration file, and read its
answers back as the plain facts the references take."""

from __future__ import annotations

from typing import Dict, Mapping, Sequence, Tuple

import numpy as np

from repro.core import (DagArrive, DagDepart, FleetController, RateChange,
                        VmAdd, VmFail)
from repro.core.dag import Dataflow, Routing
from repro.core.perfmodel import ModelLibrary, PerfModel

from reference.ticks import DagFacts

Slot = Tuple[int, int]


def library(cfg: Mapping) -> ModelLibrary:
    """The task profiles the configuration states, as the planner's
    performance models."""
    return ModelLibrary({
        kind: PerfModel.from_points(
            kind, {int(p[0]): (float(p[1]), float(p[2]), float(p[3]))
                   for p in prof["points"]}, static=bool(prof["static"]))
        for kind, prof in cfg["profiles"].items()})


def dataflow(cfg: Mapping, dag_type: str) -> Dataflow:
    spec = cfg["dags"][dag_type]
    df = Dataflow(dag_type)
    for name, kind, routing, role in spec["tasks"]:
        df.add_task(name, kind, routing=Routing(routing),
                    is_source=role == "source", is_sink=role == "sink")
    for src, dst, sel in spec["edges"]:
        df.add_edge(src, dst, float(sel))
    return df


def controller(cfg: Mapping, lib: ModelLibrary) -> FleetController:
    """The fleet controller the configuration states: on a slot budget of
    its own where it gives one, else sized to serve every DAG at its
    demand ceiling; the rate grid's step and ceiling where it gives them."""
    opts = {k: float(cfg[k]) for k in ("step", "max_rate") if k in cfg}
    if "budget_slots" in cfg:
        opts["budget_slots"] = int(cfg["budget_slots"])
    else:
        opts["self_size"] = True
    return FleetController(lib, allocator=cfg["allocator"],
                           mapper=cfg["mapper"], vm_sizes=cfg["vm_family"],
                           **opts)


def script_event(cfg: Mapping, ctl: FleetController, entry: Sequence):
    """The controller event for one script entry.  A ``fail`` kills the
    named DAG's last VM in the controller's current state."""
    kind, payload = entry
    if kind == "arrive":
        name, dag_type, weight, priority, demand = payload
        return DagArrive(name, dataflow(cfg, dag_type), weight=weight,
                         priority=priority, max_rate=demand)
    if kind == "depart":
        return DagDepart(payload)
    if kind == "rate":
        return RateChange(*payload)
    if kind == "grow":
        return VmAdd(payload)
    if kind == "fail":
        return VmFail(ctl.entry(payload).schedule.vms[-1].id)
    raise ValueError(f"unknown script event {kind!r}")


def dag_types(cfg: Mapping) -> Dict[str, str]:
    """Tenant name -> DAG type over the whole script."""
    return {p[0]: p[1] for kind, p in cfg["script"] if kind == "arrive"}


def mapping_groups(mapping) -> Dict[str, Dict[Slot, int]]:
    """Thread count per (task, slot) of a mapping, as ``(vm, slot)``."""
    out: Dict[str, Dict[Slot, int]] = {}
    for thread, slot in mapping.assignment.items():
        g = out.setdefault(thread.task, {})
        g[(slot.vm, slot.slot)] = g.get((slot.vm, slot.slot), 0) + 1
    return out


def dag_facts(cfg: Mapping, dag_type: str, name: str, mapping,
              omegas) -> DagFacts:
    spec = cfg["dags"][dag_type]
    return DagFacts(
        name=name, tasks={t[0]: t[1] for t in spec["tasks"]},
        edges=[(e[0], e[1], float(e[2])) for e in spec["edges"]],
        split={t[0]: t[2] == "split" for t in spec["tasks"]},
        groups=mapping_groups(mapping),
        vm_speed={vm.id: float(vm.speed) for vm in mapping.vms},
        omegas=np.asarray(omegas, dtype=np.float64))
