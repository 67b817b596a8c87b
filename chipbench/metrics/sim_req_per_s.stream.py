"""``sim_req_per_s`` of the stream cells, under a bound of its own: a closed
host loop of 2,048-request chunks takes the host's occasional stalls into
its rate, which a device-bound batch of replications does not."""
from chipbench import cells

read = cells.load_module("metrics", "sim_req_per_s").read
