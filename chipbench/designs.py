"""The design space of a configuration and the seeded draw of a Study's
designs (the traffic generator of this benchmark).

A configuration file names its space (square array sizes, log-spaced
operand-SRAM sizes split in thirds, dataflows, DRAM channels and
bandwidth, layout banks) and a template that fixes every other field of
a design.  A traffic mix lists a Study's slots, each fixing the array,
dataflow, DRAM and layout of one design, and a pool of SRAM sizes.
Study `k` of a run draws each slot's SRAM size from the pool, from
`(seed, k)` alone, without replacement within each program flavor
(dataflow, DRAM, layout): every Study then has the same flavors, designs
and distinct demand streams per flavor, whatever the seed.
"""
from __future__ import annotations

import copy
from typing import Dict, List, Tuple

import numpy as np

WARMUP = -1        # study index of the set-up Study


def sram_axis(cfg: Dict) -> List[int]:
    """The configuration's operand-SRAM sizes in KiB: `steps` log-spaced
    integers over [lo, hi], rounded, deduplicated, ascending."""
    a = cfg["design_space"]["sram_kb"]
    lo, hi, steps = a["lo"], a["hi"], a["steps"]
    raw = [int(round(lo * (hi / lo) ** (i / (steps - 1))))
           for i in range(steps)]
    return sorted(set(raw))


def design(cfg: Dict, slot: Dict, sram_kb: float) -> Dict:
    """One design as a plain nested dict (the fields of an accelerator
    config): the template with the slot's fields and `sram_kb` KiB of
    operand SRAM split in thirds, each the floor of a third of the whole
    bytes (409.6 KiB, 0.4 MiB, gives 139,810 B an operand)."""
    if cfg["design_space"]["sram_split"] != "thirds":
        raise ValueError(
            f"unknown SRAM split {cfg['design_space']['sram_split']!r}")
    d = copy.deepcopy(cfg["design_template"])
    d["dataflow"] = slot["dataflow"]
    d["cores"] = [dict(d["cores"][0], rows=slot["array"],
                       cols=slot["array"])]
    third = int(float(sram_kb) * 1024) // 3
    d["memory"].update(ifmap_sram_bytes=third, filter_sram_bytes=third,
                       ofmap_sram_bytes=third)
    d["dram"].update(channels=slot["channels"],
                     bandwidth_bytes_per_cycle=slot["bw"])
    if slot["layout_banks"]:
        d["layout"].update(enabled=True, num_banks=slot["layout_banks"])
    return d


def flavor(slot: Dict) -> Tuple:
    """The fields of a slot that choose its sweep program."""
    return (slot["dataflow"], slot["channels"], slot["bw"],
            slot["layout_banks"])


def plain(design: Dict) -> Dict:
    """The fields the reference reads."""
    core = design["cores"][0]
    return dict(rows=core["rows"], cols=core["cols"],
                dataflow=design["dataflow"], memory=design["memory"],
                dram=design["dram"], layout=design["layout"])


def _rng(seed: int, study: int) -> np.random.Generator:
    # seeds may exceed 32 bits and be negative; both map to one stream each
    return np.random.default_rng([seed % (1 << 64), study % (1 << 64)])


def draw(cfg: Dict, mix: Dict, seed: int, study: int) -> List[Dict]:
    """The designs of Study `study`, in the mix's slot order."""
    rng = _rng(seed, study)
    groups: Dict[Tuple, List[int]] = {}
    for i, s in enumerate(mix["slots"]):
        groups.setdefault(flavor(s), []).append(i)
    out: List[Dict] = [None] * len(mix["slots"])
    for idx in groups.values():
        kbs = rng.choice(mix["sram_kb_pool"], size=len(idx), replace=False)
        for i, kb in zip(idx, kbs):
            out[i] = design(cfg, mix["slots"][i], kb)
    return out
