"""``commit_ms`` in a cell that holds no commit time end to end: there the
save path moves ``train_tokens_per_s`` through the capture barrier and,
once a save's drain outlasts the save interval, through the next capture's
wait for host buffers."""

from chipbench import spec

read = spec.load_reader("commit_ms")
