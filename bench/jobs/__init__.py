"""The general jobs a traffic file names: ``watched`` (one training job
under PerfTracker) and ``fleet`` (a fleet of trainers under the online
diagnosis pipeline, with a seeded fault cycle)."""
