"""Fast cycles the devices stepped, as a share of what they would step
if every device ran each bucket until the bucket's slowest row exits:
``account.stepped_cycles`` / ``account.synchronous_cycles``.  Below 1
where each device's loop exits on its own rows (local exit); 1 where
every device waits for the slowest.  Nothing is read where no bucket
spans more than one device."""
from bench.lib.account import stepped_cycles, synchronous_cycles


def read(run):
    if not any(b.devices > 1 for b in run.window.buckets):
        return None
    return stepped_cycles(run) / synchronous_cycles(run)
