from repro.train.stdp_trainer import (TrainerConfig, assign_labels,
                                      assignment_accuracy, assignment_predict,
                                      evaluate, train_to_accuracy)
