"""The program's own ``ra_kv`` machine (``ra_tpu.models.kv.KvMachine``:
values live in the log, a release cursor every 256 entries,
``live_indexes`` compaction), under the name a config file can give."""


def make(args=None):
    from ra_tpu.models.kv import KvMachine

    return KvMachine(**(args or {}))
