"""``engine_us_per_kreq`` of the stream cells, moving ``sim_req_per_s.stream``."""
from chipbench import cells

read = cells.load_module("metrics", "engine_us_per_kreq").read
