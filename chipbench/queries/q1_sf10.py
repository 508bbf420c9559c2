"""TPC-H Q1 over LINEITEM at SF10, resident on the device: `q1.py`'s
DataFrame, reference and bytes, letter for letter, behind one guard.  A
cell named `_resident` must never time an upload, so `build` first asks
the program for its scan-cache bound and refuses a table that the bound
cannot hold at capacity: such a program would upload all of LINEITEM again
in every query."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_queries_q1_for_sf10",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "q1.py"))
q1 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(q1)

TABLES = q1.TABLES
reference = q1.reference
bytes_needed = q1.bytes_needed

#: the memory scan's batch: `exec/basic.py` cuts a table into batches of at
#: most 2**20 rows, each at a power-of-two capacity of at least 1,024 rows
BATCH_ROWS = 1 << 20
#: device bytes of one row at capacity: five 8-byte columns with a validity
#: byte each, two 1-char strings as 8 bytes (the smallest width bucket), a
#: 4-byte length and a validity byte each, and the batch's selection byte
ROW_BYTES_AT_CAPACITY = 5 * (8 + 1) + 2 * (8 + 4 + 1) + 1


def resident_bytes(rows):
    """Device bytes of LINEITEM's Q1 columns at `rows` rows, as pinned."""
    full, rest = divmod(rows, BATCH_ROWS)
    capacity = full * BATCH_ROWS
    if rest:
        capacity += max(1024, 1 << (rest - 1).bit_length())
    return capacity * ROW_BYTES_AT_CAPACITY


def scan_cache_bound(session):
    """The device bytes the session's scan cache may hold, as the program
    resolves them; a program from before that rule bounds it by the conf
    alone."""
    from spark_rapids_tpu.utils import scan_cache
    if hasattr(scan_cache, "resident_bound"):
        return scan_cache.resident_bound(session.conf)
    from spark_rapids_tpu.config import MEMORY_SCAN_CACHE_SIZE
    return int(session.conf.get(MEMORY_SCAN_CACHE_SIZE))


def build(session, frames):
    need = resident_bytes(frames["lineitem"].plan.source.num_rows)
    bound = scan_cache_bound(session)
    if need > bound:
        raise RuntimeError(
            f"LINEITEM's Q1 columns take {need:,} device bytes at capacity, "
            f"past the scan cache's bound of {bound:,}: every query would "
            f"upload the table again, and this cell times resident queries")
    return q1.build(session, frames)
