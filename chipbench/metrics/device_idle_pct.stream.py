"""``device_idle_pct`` of the stream cells, moving ``sim_req_per_s.stream``."""
from chipbench import cells

read = cells.load_module("metrics", "device_idle_pct").read
