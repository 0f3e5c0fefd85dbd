"""The configurations the benchmark already measured draw what they drew.

For the two configurations of BENCHMARK.json's first cells and three
seeds, at 1/50 of the cluster: SHA-256 digests of the cluster
`build_cluster` makes, of the program's nodes and standing pods, of one
incoming pod, and of the open-loop arrivals of every traffic file that
configuration runs (the window's and the warm-up's). The pinned digests were recorded on the tree before
the harness learnt affinity terms, standing groups and incoming churn, so
a change to the draws of the old forms shows here.

    python -m benchmark.tests.test_unchanged   # prints the digests
"""
import dataclasses
import hashlib
import json
import os

import pytest

from benchmark import traffic as tr
from benchmark import workload as wl

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = (7, 2147501041, 4000000007)
SCALE = 50


def _sha(obj) -> str:
    text = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()


def _plain(obj):
    """A program object as plain data, without its process-local uid."""
    d = dataclasses.asdict(obj)
    d["metadata"].pop("uid")
    return d


def _config(name: str) -> tuple:
    """(configuration, the traffic files its cells run)."""
    bench = wl.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}[name]
    cells = [w for w in bench["workloads"] if w["config"] == name]
    return (wl.load_json(os.path.join(ROOT, entry["file"])),
            sorted({w["traffic"] for w in cells}))


def digests(name: str, seed: int) -> dict:
    cfg, traffics = _config(name)
    c = wl.build_cluster(cfg, seed, SCALE)
    incoming = cfg["pod_templates"][cfg["incoming"]["template"]]
    out = {
        "cluster": _sha([c.node_names, c.node_labels, c.node_template,
                         c.resources, c.alloc.tolist(), c.templates,
                         c.standing_keys, c.standing_node.tolist()]),
        "nodes": _sha([_plain(n) for n in wl.to_program_nodes(c)]),
        "standing": _sha([_plain(p) for p in wl.to_program_standing(c)]),
        "incoming": _sha(_plain(wl.to_program_pod(incoming, "pod-0"))),
    }
    for t in traffics:
        spec = wl.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                         t + ".json"))
        if spec["arrivals"] == "closed":
            continue
        out["arrivals " + t] = _sha(
            [tr.arrivals(spec, 51.0, seed, SCALE).tolist(),
             tr.arrivals(spec, 3600.0, seed + 1, SCALE).tolist()])
    return out


PINNED = {
    ('sched-perf-basic-5k', 7): {
        'cluster':
            '44d9911229f33c5daed8c65478259a5e555207399a947c46fa61addb095ec89f',
        'nodes':
            'a6850fb27556a3af4b05256d3d4f69b3e8db2ce97ce264a2d443b25efc2a958e',
        'standing':
            'c2b1560eea72a4fa96e292441bf53dc75ce0078617f2ad99d196fc151f0cc3ee',
        'incoming':
            'df6b808bdf35f9900c1adcdbd1f666c7cd57c1e9b8e15acaa2a27f82e75cecee',
        'arrivals poisson-basic-5k':
            '5c7d5e0344aad21b59b4dcc91a3c4a16ee62654a9bfef6adcfc2944cf0179b7a',
    },
    ('sched-perf-basic-5k', 2147501041): {
        'cluster':
            '748c9a65a65ba281096f6d37951e68375d16b44b685295916f77d3f9fde78d21',
        'nodes':
            'a6850fb27556a3af4b05256d3d4f69b3e8db2ce97ce264a2d443b25efc2a958e',
        'standing':
            'b94b1b8747556ff5f5fb0787751425fbe5718dff715f593f50e2eccfb4fc29dc',
        'incoming':
            'df6b808bdf35f9900c1adcdbd1f666c7cd57c1e9b8e15acaa2a27f82e75cecee',
        'arrivals poisson-basic-5k':
            '7b621d64f7171d06b9bffe602759bff1d58f812b040059428e206d01a0b6cfcd',
    },
    ('sched-perf-basic-5k', 4000000007): {
        'cluster':
            '76ab972c4d9850afee8cb4772bf2d4e971671fc95ebb18f8ad0dfc250b184422',
        'nodes':
            'a6850fb27556a3af4b05256d3d4f69b3e8db2ce97ce264a2d443b25efc2a958e',
        'standing':
            '698a4b819f3753759e22c719bb2282fe57eaf70025e65abbd18c3fa7981fbfd2',
        'incoming':
            'df6b808bdf35f9900c1adcdbd1f666c7cd57c1e9b8e15acaa2a27f82e75cecee',
        'arrivals poisson-basic-5k':
            '150dcd6577546911efea31ad2c6fb1faa26f6b20dbf56c2af1008b9b0b3d3f0e',
    },
    ('sched-perf-spread-5k', 7): {
        'cluster':
            '0fbeeb44249ceaa1a95ec8104fddbaf88db74e0a468014a440239d2ffa3de1bd',
        'nodes':
            '39c0bcbc126bee78471ce56cf52c6b405748c27822f2208cf614933feb03399e',
        'standing':
            'a91887f6b1e5141c39cbbb4bf177f665170cb8e4d74b84dd1125237d46373496',
        'incoming':
            '1b350328e535ae25a560bb70b8b427a618cba4184c4742be15a7bdd35658ebb5',
        'arrivals poisson-spread-5k':
            '0a8dd844ff9813c207fb1d86b22440e7d4dbf4a0dea8909b4b98bcfc3370db06',
    },
    ('sched-perf-spread-5k', 2147501041): {
        'cluster':
            '2399b42266266846449d0277d4dea52a4b97f4decfb5a1e0a4b130e25715d319',
        'nodes':
            'c2de8153df924fe8cd3372df14e51b095fce9013edd2a01582b0f0181716118c',
        'standing':
            '7ec83f3b5fcc720d4e1f81e4325fb329a4f05c49cd14ff5ed01ea64461fdd28d',
        'incoming':
            '1b350328e535ae25a560bb70b8b427a618cba4184c4742be15a7bdd35658ebb5',
        'arrivals poisson-spread-5k':
            'd20b315dd1575a9645353b93c7805505dd2ce95286afe73e7cba08166dfb2b19',
    },
    ('sched-perf-spread-5k', 4000000007): {
        'cluster':
            'bc0d1bcadd283332bf7f9587faaa7ede9f3caea949722f8f29cac938a36eafa8',
        'nodes':
            'e82587f4c8481284417288240dcc1110f0c8245b39df44ff380bf8cbaa3e8d47',
        'standing':
            'f1628421ef3617d34e6114d6df966a78479489dcf478b7d0920e61b2de4d0332',
        'incoming':
            '1b350328e535ae25a560bb70b8b427a618cba4184c4742be15a7bdd35658ebb5',
        'arrivals poisson-spread-5k':
            '7b06ddaf1e80e014de89c82de6e672b6a2e7c5e0ae1841f11de9a5a683595c2b',
    },
}


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_the_old_forms_draw_what_they_drew(name, seed):
    assert digests(name, seed) == PINNED[(name, seed)]


if __name__ == "__main__":
    for n in sorted({n for n, _s in PINNED}):
        for s in SEEDS:
            print(f"    ({n!r}, {s}): {digests(n, s)!r},")
