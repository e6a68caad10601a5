"""Small versions of the benchmark's cells for runs on the CPU."""
import copy

from bench.catalog import load_cell


def tiny_cell(name: str, tenants: int = 32):
    """``name`` at ``tenants`` tenants and as many devices per type, a 300 s
    warm-up."""
    cell = copy.copy(load_cell(name))
    cell.config = dict(cell.config, tenants=tenants,
                       devices_per_type=[tenants] * len(cell.config["device_types"]),
                       reference_sample=4)
    cell.traffic = dict(cell.traffic, warmup_s=300.0, duration_s=20000.0)
    return cell
