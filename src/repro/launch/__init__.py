"""Launchers: host meshes, the continuous-batching engine, runnable
train/serve drivers, and the EpitomePlan CLI
(`python -m repro.launch.plan` — search | legalize | show | run)."""
