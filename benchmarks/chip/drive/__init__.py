"""Traffic drivers found by name: ``<driver>.py`` holds class ``Driver``."""
