"""One driver per kind of entry driven; a traffic mix names its driver."""
