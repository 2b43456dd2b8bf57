"""The benchmark's harness: data in ``../configs``, ``../traffic`` and
``../metrics`` decides what runs; the modules here are the yardstick."""
