"""The clusters the generators make, pinned by digest: the same
configuration and seed give the same cluster, byte for byte, wherever
its generator's code lives."""

import hashlib
import json

import pytest

from helpers import config, rehearsal

import gen

# sha256 of the canonical JSON of gen.make_cluster, as each generator read
# before it moved into a file of its own.  "full" is the configuration as
# committed: 5,000 nodes, and 100,000 resident pods in k8s-large-5k.
PINS = [
    ("k8s-large-5k", "rehearsal", 11,
     "5b9f682d834d3047ad33bd172c031934477657718324e0477b15345c6d61b629"),
    ("k8s-large-5k", "rehearsal", 4000000001,
     "f79eb3c56a5deb164e04452af5a73cda5b236d8da34dddf69cba568187d96b6d"),
    ("k8s-large-5k", "full", 2200000001,
     "44a1fb21f9b527cfeaed21280cf9e7e1bf50546550873062076f0dc4ec61652f"),
    ("sched-perf-5k", "rehearsal", 11,
     "7e23f18d9fe32d5c997b44242449474e60fb087efa0e0968a3d31dd6ac94092b"),
    ("sched-perf-5k", "rehearsal", 4000000001,
     "2c19470bd6bcdd50fcf0f1a6703ed1c5cd7e5c980ca87af6277f9960d149dd08"),
    ("sched-perf-5k", "full", 2200000001,
     "09a3330b454b88f79586a3bf7e919b00c509123e98d133b41d6a95910c8d5c33"),
    ("sched-perf-5k-antiaffinity", "rehearsal", 11,
     "6aa0e3a76353eb647660a6f02d7f5b6265906aeb3b6ef8d5e50fae710f3d6bba"),
    ("sched-perf-5k-antiaffinity", "rehearsal", 4000000001,
     "324684c54d68e590de23260296c1f462a23a4684f42f4acbcbb227f7b074a4e7"),
    ("sched-perf-5k-antiaffinity", "full", 2200000001,
     "4e93ec3272f0be24d8a65222b4d87f4c219b433580580ee8d75305454ad22bf1"),
]


@pytest.mark.parametrize("name,size,seed,digest", PINS)
def test_cluster_digest(name, size, seed, digest):
    cfg = config(name)
    if size == "rehearsal":
        cfg.update(rehearsal(name)["config"])
    cluster = gen.make_cluster(cfg, seed)
    text = json.dumps(cluster, sort_keys=True, separators=(",", ":"))
    assert hashlib.sha256(text.encode()).hexdigest() == digest
