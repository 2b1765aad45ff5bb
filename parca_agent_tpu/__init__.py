"""parca_agent_tpu — a TPU-native, whole-machine sampling profiler framework.

A ground-up re-design of the capabilities of parca-agent (reference:
/root/reference, see SURVEY.md): always-on 100 Hz stack sampling, windowed
aggregation of (pid, stack) -> count into labeled pprof profiles, address ->
symbol resolution (kallsyms / JIT perf maps / ELF normalization), DWARF
unwind-table building, target discovery and metadata labeling, and batched
remote write — with the per-window profile-build hot loop re-expressed as a
batched JAX/XLA program (radix-hash + segment reductions + count-min/HLL
sketches over all PIDs at once) that runs on TPU and merges across a device
mesh with XLA collectives.

Layer map (mirrors SURVEY.md section 1, re-architected TPU-first):

  capture/     window snapshot data contracts, synthetic/replay/perf sources
  aggregator/  pluggable Aggregator: CPU (numpy oracle) and the device-resident
               stack dictionary (JAX), alone or sharded over a mesh
  ops/         row hashing (numpy/JAX twins), count-min and HLL sketches
  pprof/       pprof profile.proto wire encoder + profile builder
  symbolize/   kallsyms, JIT perf maps, /proc/maps, ELF bases, build IDs
  unwind/      .eh_frame -> compact fixed-width unwind tables
  discovery/   target discovery manager (procfs, systemd, k8s)
  metadata/    label providers + Prometheus-style relabeling
  transport/   batched, retrying remote write; local file store
  debuginfo/   debuginfo find / extract / upload
  agent/       the agent shell: config, main loop, HTTP status + metrics
  parallel/    device mesh layout and fleet (multi-host) sketch merge
  native/      C++ runtime pieces behind a C ABI (capture, codecs)
"""

__version__ = "0.1.0"
