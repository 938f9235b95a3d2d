"""CassandraKeyValue: ``(k text primary key, v blob)``, every value a
function of (seed, key number, version), so that every read can be checked.

The yardstick for the ``cassandra_keyvalue_rf3`` deployment; imports
nothing of the program. Keys are the upstream app's ``key:<n>``. A value
is 64 bytes: the version as 8 big-endian bytes, then 56 bytes of BLAKE2b
over (seed, n, version). A read is sound when the value is the one this
function gives for the version it carries (nothing torn, nothing from
another key), that version is no older than the last one acknowledged
before the read was sent, and no newer than the newest one sent. All
comparisons are exact: limit 0.
"""

from __future__ import annotations

import hashlib
import struct

VALUE_BYTES = 64
_PACK = struct.Struct(">qqq")
_VER = struct.Struct(">q")


def key_of(n: int) -> str:
    return f"key:{n}"


def value(seed: int, n: int, version: int) -> bytes:
    return _VER.pack(version) + hashlib.blake2b(
        _PACK.pack(seed, n, version), digest_size=VALUE_BYTES - 8).digest()


def version_of(v: bytes | None) -> int | None:
    """The version a value claims, or None for a value of the wrong size."""
    if v is None or len(v) != VALUE_BYTES:
        return None
    return _VER.unpack_from(v)[0]


def read_is_sound(seed: int, n: int, got: bytes | None, acked_before: int,
                  sent_by_reply: int) -> bool:
    ver = version_of(got)
    return (ver is not None and acked_before <= ver <= sent_by_reply
            and got == value(seed, n, ver))


class Reference:
    def __init__(self, config: dict, seed: int):
        self.seed = seed
        self.keys = int(config["scale"]["keys"])
        self.table = config["schema"]["table"]
        self.ddl = config["schema"]["ddl"]
        self.batch = int(config["load"]["batch_ops"])
        # The last ``burst_rows`` keys are loaded once the flush threshold
        # is set, so that every replica's memtable crosses it once and
        # the flush program is compiled before the window; the table
        # holds exactly ``keys`` when the window opens.
        self.burst = int(config["load"].get("burst_rows", 0))
        self.burst_batch = int(config["load"].get("burst_batch_ops", 1024))

    def batches(self, phase: str = "preload"):
        lo, hi, step = ((0, self.keys - self.burst, self.batch)
                        if phase == "preload" else
                        (self.keys - self.burst, self.keys, self.burst_batch))
        for at in range(lo, hi, step):
            yield [{"k": key_of(n), "v": value(self.seed, n, 0)}
                   for n in range(at, min(at + step, hi))]

    def check(self, answers: list[dict]) -> dict:
        """The generator's children compare each read as they hand it
        back (they hold the acknowledgement order); what arrives here is
        their tallies, summed by the harness. Nothing more to compare."""
        return {"compared": {}, "wrong": 0, "examples": []}

    def check_replicas(self, sample: list[list], read) -> dict:
        """``sample``: [[n, version]] acknowledged in the window;
        ``read(key_values)`` gives {replica: {key: value bytes}} straight
        from each replica's engine, once nothing is left to apply. Every
        replica must hold every sampled write: RF=3 means three copies."""
        rows = read([{"k": key_of(n)} for n, _v in sample])
        bad = [f"{replica} {key_of(n)} v{ver}"
               for replica, got in sorted(rows.items())
               for n, ver in sample
               if got.get(key_of(n)) != value(self.seed, n, ver)]
        return {"compared": {
                    "replica_rows_checked": [len(sample) * len(rows), None],
                    "replica_rows_wrong": [len(bad), 0]},
                "wrong": len(bad), "examples": bad[:3]}


def control(config: dict, seed: int, traffic: dict, operations: int = 100_000):
    """A window's worth of reads of keys that were each overwritten once
    and acknowledged (version 1), answered (a) soundly and (b) with one
    guarantee broken in turn: a stale read (version 0 after version 1
    was acknowledged), a torn value (half of each version), a value of
    another key, and a lost acknowledged write (nothing comes back)."""
    import random

    rng = random.Random(f"control/{seed}")
    keys = int(config["scale"]["keys"])
    ns = [rng.randrange(keys) for _ in range(operations)]
    sound = sum(not read_is_sound(seed, n, value(seed, n, 1), 1, 1)
                for n in ns)
    broken = {
        "stale_read": lambda n: value(seed, n, 0),
        "torn_value": lambda n: value(seed, n, 1)[:32] + value(seed, n, 0)[32:],
        "other_keys_value": lambda n: value(seed, (n + 1) % keys, 1),
        "lost_write": lambda n: None,
    }
    caught = {name: sum(not read_is_sound(seed, n, f(n), 1, 1) for n in ns)
              for name, f in broken.items()}
    return {"operations": operations, "sound_wrong": sound,
            "control_wrong": min(caught.values()),
            "control_wrong_by_guarantee": caught,
            "control": "stale read / torn value / other key / lost write"}
