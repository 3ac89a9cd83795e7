"""The benchmark's yardstick: cell loading, data generation, trace reduction."""
